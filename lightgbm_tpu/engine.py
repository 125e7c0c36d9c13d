"""Training engine: train() and cv().

Behavioral analog of ref: python-package/lightgbm/engine.py (train :25,
cv :399, CVBooster :285, _make_n_folds :323).
"""
from __future__ import annotations

import collections
import contextlib
import copy
from typing import Any, Dict, List, Optional, Union

import numpy as np

from . import callback as callback_mod
from .basic import Booster, Dataset
from .config import Config
from .obs.registry import Span
from .utils import log

__all__ = ["train", "cv", "CVBooster"]

_ROUND_ALIASES = ("num_iterations", "num_iteration", "n_iter", "num_tree",
                  "num_trees", "num_round", "num_rounds", "nrounds",
                  "num_boost_round", "n_estimators", "max_iter")
_ES_ALIASES = ("early_stopping_round", "early_stopping_rounds",
               "early_stopping", "n_iter_no_change")


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          fobj=None, feval=None,
          init_model: Optional[Union[str, Booster]] = None,
          feature_name="auto", categorical_feature="auto",
          keep_training_booster: bool = False,
          callbacks: Optional[List] = None,
          resume_from: Optional[str] = None) -> Booster:
    """Train a booster (ref: engine.py:25).

    ``resume_from`` restores a run from a resilience checkpoint (a
    ``ckpt_<iteration>`` directory or a ``checkpoint_dir`` root — the
    newest complete one is selected) and continues it bit-identically
    to an uninterrupted run with the same params/seed; pass the same
    dataset, valid sets and callbacks the interrupted run used
    (docs/Reliability.md). The ``resume`` params key is equivalent."""
    # the whole call is the span ``train``; until the Booster exists its
    # children close into a list, which the Booster's registry then takes
    with Span(None, "train", hold=[]) as span:
        return _train(span, params, train_set, num_boost_round, valid_sets,
                      valid_names, fobj, feval, init_model, feature_name,
                      categorical_feature, callbacks, resume_from)


def _train(span: Span, params, train_set, num_boost_round, valid_sets,
           valid_names, fobj, feval, init_model, feature_name,
           categorical_feature, callbacks, resume_from) -> Booster:
    params = dict(params) if params else {}
    # pop BOTH keys unconditionally: a resume path left in params would
    # echo into the serialized model's parameters block and break the
    # bit-identical-serialization contract below
    _p_resume = params.pop("resume", "") or params.pop("resume_from", "")
    params.pop("resume_from", None)
    if resume_from and _p_resume and str(_p_resume) != str(resume_from):
        log.warning("resume_from=%s overrides params resume=%s",
                    resume_from, _p_resume)
    resume_from = resume_from or _p_resume or None
    if train_set is not None and isinstance(getattr(train_set, "params",
                                                    None), dict):
        # the resume path is a per-invocation instruction, not a model
        # property: scrub it from the dataset params too so the
        # resumed model's echoed parameters block (and hence its
        # serialization) stays identical to an uninterrupted run's
        for key in ("resume", "resume_from"):
            train_set.params.pop(key, None)
    if resume_from and init_model is not None:
        log.warning("resume_from and init_model both given; resume wins "
                    "(the checkpoint already contains the full model)")
        init_model = None
    # resolve num_boost_round / early stopping aliases (params win)
    for alias in _ROUND_ALIASES:
        if alias in params:
            num_boost_round = int(params.pop(alias))
    params["num_iterations"] = num_boost_round
    snapshot_freq = int(params.get("snapshot_freq",
                                   params.get("save_period", -1) or -1))
    snapshot_base = str(params.get("output_model", "LightGBM_model.txt"))
    first_metric_only = bool(params.get("first_metric_only", False))
    early_stopping_round = None
    for alias in _ES_ALIASES:
        if alias in params:
            early_stopping_round = int(params[alias])

    if feature_name != "auto":
        train_set.feature_name = feature_name
    if categorical_feature != "auto":
        train_set.categorical_feature = categorical_feature

    # continued training: init model's raw predictions become init scores
    predictor = None
    if isinstance(init_model, str):
        predictor = Booster(model_file=init_model)
    elif isinstance(init_model, Booster):
        # num_iteration=-1: continuation must see EVERY tree, including
        # the post-best overrun of an early-stopped init_model (the
        # default would truncate to best_iteration)
        predictor = Booster(model_str=init_model.model_to_string(
            num_iteration=-1))
    if predictor is not None and train_set.init_score is None:
        raw = predictor.predict(train_set.data, raw_score=True)
        train_set.set_init_score(np.asarray(raw).reshape(-1, order="F"))

    # train_set appearing in valid_sets enables training metrics
    # (ref: engine.py train_data_name handling)
    if valid_sets is not None:
        vs_list = valid_sets if isinstance(valid_sets, list) else [valid_sets]
        if any(vs is train_set for vs in vs_list):
            params.setdefault("is_provide_training_metric", True)

    with Span(None, "train/booster_init"):
        booster = Booster(params=params, train_set=train_set)
    tel = booster._gbdt.telemetry
    span.bind(tel)
    if valid_sets is not None:
        if not isinstance(valid_sets, list):
            valid_sets = [valid_sets]
        with tel.timed("train/valid_sets", sets=len(valid_sets)):
            for i, vs in enumerate(valid_sets):
                if vs is train_set:
                    name = "training"
                elif valid_names is not None and i < len(valid_names):
                    name = valid_names[i]
                else:
                    name = f"valid_{i}"
                if vs is not train_set:
                    if predictor is not None and vs.init_score is None:
                        raw = predictor.predict(vs.data, raw_score=True)
                        vs.set_init_score(
                            np.asarray(raw).reshape(-1, order="F"))
                    booster.add_valid(vs, name)
    train_in_valid = valid_sets is not None and any(
        vs is train_set for vs in valid_sets)

    callbacks = list(callbacks) if callbacks else []
    if early_stopping_round is not None and early_stopping_round > 0:
        callbacks.append(callback_mod.early_stopping(
            early_stopping_round, first_metric_only, verbose=True))
    callbacks_before = [cb for cb in callbacks
                        if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks
                       if not getattr(cb, "before_iteration", False)]
    callbacks_before.sort(key=lambda cb: getattr(cb, "order", 0))
    callbacks_after.sort(key=lambda cb: getattr(cb, "order", 0))

    # main loop (ref: engine.py:260-283)
    # Megastep arming: this loop may consume multi-iteration steps (one
    # jit fusing up to tpu_megastep_iters iterations) because it breaks
    # on `finished` and nothing here needs per-iteration observation.
    #
    # Per-iteration consumers no longer force the synchronous path when
    # they are the BUILT-IN set (early_stopping / log_evaluation /
    # record_evaluation / record_telemetry, plus snapshot_freq): the
    # megastep evaluates every configured metric ON DEVICE inside the
    # scan (metric/traced.py) and the drain replays these callbacks in
    # iteration order against the stacked metric matrix
    # (callback.DrainEvalReplay) — no score fetch, no re-predict, and a
    # scan-carried early-stop flag keeps the drained model bit-identical
    # to this loop's synchronous early-stopped model. Anything the drain
    # cannot replay (user callbacks, reset_parameter, feval, fobj, an
    # untraceable metric) falls back to the classic inline loop below,
    # with a structured megastep_evicted event naming the blocker.
    gbdt = booster._gbdt
    consumer = None
    # (the drain-replay consumer and the traced metric plan)
    with tel.timed("train/callbacks_plan"):
        want_replay = bool(callbacks) or snapshot_freq > 0
        if want_replay and feval is None and fobj is None:
            blocker = callback_mod.drain_replay_blocker(
                callbacks_before + callbacks_after)
            if blocker is None:
                ok, blocker = gbdt.megastep_eval_precheck(
                    include_training=train_in_valid,
                    es_spec=callback_mod.find_es_spec(callbacks_after))
                if ok:
                    consumer = callback_mod.DrainEvalReplay(
                        booster=booster, params=params,
                        callbacks_before=callbacks_before,
                        callbacks_after=callbacks_after,
                        end_iteration=num_boost_round,
                        snapshot_freq=snapshot_freq,
                        snapshot_base=snapshot_base,
                        include_training=train_in_valid)
                    gbdt.arm_megastep(True, eval_consumer=consumer)
            if consumer is None:
                gbdt._report_eviction(blocker, stage="engine")
        elif want_replay or feval is not None or fobj is not None:
            gbdt._report_eviction("feval" if feval is not None else "fobj",
                                  stage="engine")
        if consumer is None and not callbacks and feval is None \
                and fobj is None and snapshot_freq <= 0:
            gbdt.arm_megastep(True)
    evaluation_result_list: List = []
    start_iteration = 0
    if resume_from:
        # restore AFTER valid sets were added and the megastep consumer
        # was armed: the score-carry shapes and the traced eval plan are
        # settled, so the checkpoint slots can be matched against them
        from .resilience import state as rstate
        payload = rstate.restore_into_booster(booster, str(resume_from))
        start_iteration = gbdt.iter
        saved_eval = rstate.eval_list_from_payload(payload)
        env = callback_mod.CallbackEnv(
            model=booster, params=params,
            iteration=max(0, start_iteration - 1), begin_iteration=0,
            end_iteration=num_boost_round,
            evaluation_result_list=saved_eval)
        es_state = rstate.restore_callback_states(
            callbacks_before + callbacks_after,
            (payload.get("engine_extra") or {}).get("callbacks") or [],
            env)
        evaluation_result_list = list(saved_eval)
        if consumer is not None:
            consumer.last_eval = list(saved_eval)
            if es_state is not None:
                # rebuild the scan's device early-stop carry from the
                # restored callback state (same f32 values + compares)
                rstate.synthesize_es_carry(gbdt, es_state)
    if gbdt._ckpt is not None:
        # checkpoint extra-state hook: the callback closures' early-stop
        # lists and the last eval list ride every checkpoint so the
        # restore above has them on the other side
        def _engine_ckpt_extra():
            from .resilience import state as rstate
            ev = (list(consumer.last_eval) if consumer is not None
                  else list(evaluation_result_list))
            return {"callbacks": rstate.callback_states(
                        callbacks_before + callbacks_after),
                    "eval_list": [list(t) for t in ev]}
        gbdt.set_checkpoint_extra(_engine_ckpt_extra)
    i = -1
    # the span ``finish``: from the return of the last update to the
    # return of this call
    finish = contextlib.ExitStack()
    try:
      for i in range(start_iteration, num_boost_round):
        try:
            if consumer is not None:
                finished = booster.update()
                if gbdt._eval_consumer is None and consumer.stop is None:
                    # defensive fallback (see GBDT.train_one_iter):
                    # resume classic inline evaluation from here on
                    consumer = None
                    continue
                if consumer.stop is not None:
                    booster.best_iteration = consumer.stop[0] + 1
                    evaluation_result_list = consumer.stop[1]
                    break
                evaluation_result_list = list(consumer.last_eval)
                if finished:
                    break
                continue
            for cb in callbacks_before:
                cb(callback_mod.CallbackEnv(
                    model=booster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=None))
            finished = booster.update(fobj=fobj)
            if snapshot_freq > 0 and (i + 1) % snapshot_freq == 0:
                # periodic checkpoint (ref: gbdt.cpp:279-283
                # SaveModelToFile snapshot_out); the text model is the
                # checkpoint format — snapshots are resume checkpoints:
                # keep the full model
                booster.save_model(
                    f"{snapshot_base}.snapshot_iter_{i + 1}",
                    num_iteration=-1)

            evaluation_result_list = []
            if valid_sets is not None or feval is not None:
                if train_in_valid or (feval is not None
                                      and booster._gbdt.training_metrics):
                    evaluation_result_list.extend(
                        booster.eval_train(feval))
                evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in callbacks_after:
                    cb(callback_mod.CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=0, end_iteration=num_boost_round,
                        evaluation_result_list=evaluation_result_list))
            except callback_mod.EarlyStopException as es:
                booster.best_iteration = es.best_iteration + 1
                evaluation_result_list = es.best_score
                break
            # sync-driver checkpoint cadence: the iteration is fully
            # settled here (update + snapshot + eval + callbacks), so
            # the captured callback state matches the captured model
            gbdt.maybe_checkpoint()
            if finished:
                break
        except callback_mod.EarlyStopException:
            raise   # control flow, not a crash
        except BaseException as exc:
            # crash flight recorder: anything unwinding out of the train
            # loop — the update itself, a callback, eval, or a snapshot
            # write — lands the ring buffer + section stack + config in
            # <telemetry_out>.crash.json before reaching the caller.
            # BaseException, not Exception: Ctrl-C on a wedged run is
            # the flight recorder's primary "where was it stuck" case
            booster._dump_crash(exc)
            raise
      finish.enter_context(tel.timed("finish"))
    finally:
        # a kept booster must return to the one-iteration-per-update
        # contract once this loop stops consuming multi-iteration steps
        # (disarming with a consumer bound drains + replays the tail
        # first, so no queued metric rows are dropped)
        with tel.timed("finish/drain"):
            booster._gbdt.arm_megastep(False)
        booster._gbdt.set_checkpoint_extra(None)

    with finish:
        if consumer is not None:
            # the tail drain above may have replayed the final iterations —
            # pick up a late early-stop verdict or the last eval list
            if consumer.stop is not None and booster.best_iteration <= 0:
                booster.best_iteration = consumer.stop[0] + 1
                evaluation_result_list = consumer.stop[1]
            elif consumer.last_eval and not evaluation_result_list:
                evaluation_result_list = list(consumer.last_eval)

        booster.best_score = collections.defaultdict(collections.OrderedDict)
        for name, metric, value, _ in (evaluation_result_list or []):
            booster.best_score[name][metric] = value
        # observability epilogue: stop an open profiler trace, write the
        # telemetry summary + flush the JSONL sink, then let callbacks with a
        # finalize hook (record_telemetry) drain the completed records
        booster._finalize_telemetry()
        with tel.timed("finish/callbacks"):
            for cb in callbacks_before + callbacks_after:
                fin = getattr(cb, "finalize", None)
                if fin is not None:
                    fin(callback_mod.CallbackEnv(
                        model=booster, params=params, iteration=i,
                        begin_iteration=0, end_iteration=num_boost_round,
                        evaluation_result_list=evaluation_result_list))
        return booster


class CVBooster:
    """Container of per-fold boosters (ref: engine.py:285)."""

    def __init__(self):
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def _append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]
        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params: Dict,
                  seed: int, stratified: bool, shuffle: bool):
    """(ref: engine.py:323)"""
    full_data = full_data.construct()
    num_data = full_data.num_data()
    if folds is not None:
        if not hasattr(folds, "__iter__") and not hasattr(folds, "split"):
            raise AttributeError(
                "folds should be a generator or iterator of (train_idx, "
                "test_idx) tuples or scikit-learn splitter object")
        if hasattr(folds, "split"):
            group_info = full_data.get_field("group")
            if group_info is not None:
                group_sizes = np.diff(group_info)
                flattened = np.repeat(np.arange(len(group_sizes)),
                                      group_sizes)
            else:
                flattened = None
            folds = folds.split(X=np.empty(num_data), y=full_data.get_label(),
                                groups=flattened)
        return list(folds)
    rng = np.random.RandomState(seed)
    if stratified:
        label = np.asarray(full_data.get_label())
        classes = np.unique(label)
        test_folds = np.zeros(num_data, np.int32)
        for c in classes:
            idx = np.nonzero(label == c)[0]
            if shuffle:
                rng.shuffle(idx)
            test_folds[idx] = np.arange(len(idx)) % nfold
        return [(np.nonzero(test_folds != f)[0], np.nonzero(test_folds == f)[0])
                for f in range(nfold)]
    group_info = full_data.get_field("group")
    if group_info is not None:
        # fold by whole queries (ref: engine.py group-aware kfold)
        num_groups = len(group_info) - 1
        gidx = np.arange(num_groups)
        if shuffle:
            rng.shuffle(gidx)
        splits = np.array_split(gidx, nfold)
        boundaries = np.asarray(group_info)
        out = []
        for f in range(nfold):
            test_groups = set(splits[f].tolist())
            test_mask = np.zeros(num_data, bool)
            for g in test_groups:
                test_mask[boundaries[g]:boundaries[g + 1]] = True
            out.append((np.nonzero(~test_mask)[0], np.nonzero(test_mask)[0]))
        return out
    idx = np.arange(num_data)
    if shuffle:
        rng.shuffle(idx)
    splits = np.array_split(idx, nfold)
    return [(np.concatenate([splits[j] for j in range(nfold) if j != f]),
             splits[f]) for f in range(nfold)]


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True,
       shuffle: bool = True, metrics=None, feval=None, init_model=None,
       feature_name="auto", categorical_feature="auto",
       fpreproc=None, seed: int = 0, callbacks=None,
       eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """Cross-validation (ref: engine.py:399)."""
    params = dict(params) if params else {}
    for alias in _ROUND_ALIASES:
        if alias in params:
            num_boost_round = int(params.pop(alias))
    if metrics is not None:
        params["metric"] = metrics
    obj = str(params.get("objective", "regression"))
    if stratified and not obj.startswith(("binary", "multiclass")):
        stratified = False

    train_set.construct()
    fold_splits = _make_n_folds(train_set, folds, nfold, params, seed,
                                stratified, shuffle)
    cvbooster = CVBooster()
    fold_data = []
    for train_idx, test_idx in fold_splits:
        tr = train_set.subset(train_idx)
        te = train_set.subset(test_idx, )
        if fpreproc is not None:
            tr, te, params = fpreproc(tr, te, dict(params))
        booster = Booster(params=dict(params), train_set=tr)
        booster.add_valid(te, "valid")
        if eval_train_metric:
            booster._gbdt.training_metrics = booster._make_metrics(tr._inner)
        cvbooster._append(booster)
        fold_data.append((tr, te))

    callbacks = list(callbacks) if callbacks else []
    es_round = None
    for alias in _ES_ALIASES:
        if alias in params:
            es_round = int(params[alias])
    if es_round is not None and es_round > 0:
        callbacks.append(callback_mod.early_stopping(
            es_round, bool(params.get("first_metric_only", False)),
            verbose=False))
    callbacks_before = [cb for cb in callbacks
                        if getattr(cb, "before_iteration", False)]
    callbacks_after = [cb for cb in callbacks
                       if not getattr(cb, "before_iteration", False)]

    results = collections.defaultdict(list)
    for i in range(num_boost_round):
        for cb in callbacks_before:
            cb(callback_mod.CallbackEnv(
                model=cvbooster, params=params, iteration=i,
                begin_iteration=0, end_iteration=num_boost_round,
                evaluation_result_list=None))
        agg: Dict[str, List[float]] = collections.defaultdict(list)
        bigger: Dict[str, bool] = {}
        for booster in cvbooster.boosters:
            booster.update()
            for name, metric, value, hb in (booster.eval_train(feval)
                                            if eval_train_metric else []) \
                    + booster.eval_valid(feval):
                agg[f"{name} {metric}"].append(value)
                bigger[f"{name} {metric}"] = hb
        res_list = []
        for key, vals in agg.items():
            mean, std = float(np.mean(vals)), float(np.std(vals))
            results[key + "-mean"].append(mean)
            results[key + "-stdv"].append(std)
            res_list.append(("cv_agg", key, mean, bigger[key]))
        try:
            for cb in callbacks_after:
                cb(callback_mod.CallbackEnv(
                    model=cvbooster, params=params, iteration=i,
                    begin_iteration=0, end_iteration=num_boost_round,
                    evaluation_result_list=res_list))
        except callback_mod.EarlyStopException as es:
            cvbooster.best_iteration = es.best_iteration + 1
            for key in list(results):
                results[key] = results[key][:cvbooster.best_iteration]
            break
    out = dict(results)
    if return_cvbooster:
        out["cvbooster"] = cvbooster
    return out
