"""Capture/restore of the GBDT driver's complete resumable state.

The checkpoint snapshot is everything the driver needs to continue a
run BIT-IDENTICALLY to one that was never interrupted:

- the materialized model (every HostTree's arrays, float64 — binary
  exact, no text round-trip);
- the train/valid score carries at the capture boundary (f32 device
  buffers pulled to host; restoring them by value is what makes resume
  exact — replaying trees would re-accumulate in a different f32 order);
- the bagging block-LCG stream positions, the live in-bag weight
  vector, the feature-fraction LCG position, and the boosting-mode
  extras (GOSS's MT19937, DART's drop stream + tree weights);
- early-stopping state: the driver-level best dicts (CLI loop) plus,
  via the engine's extra-state hook, the callback closures' best lists
  (engine loop; the megastep's device early-stop carry is synthesized
  back from those — see :func:`synthesize_es_carry`);
- telemetry counters, so dashboards survive a respawn without resets.

Capture runs at a drain boundary (the one host sync point the fast path
has), so the score fetch rides the sync that already happened; the
actual file I/O is the background writer's (checkpoint.py).

Multi-process: each rank captures its OWN row block of the sharded
train-score carry (``MultiProcLayout.local_block``) and restores it
with ``shard_local_cols`` — checkpoints are per-rank files selected as
a hash-consistent set by the launcher.
"""
from __future__ import annotations

import copy
import json
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..models.tree import HostTree
from ..obs.drift import add_score_distribution, profile_digest
from ..obs.health import model_state_hash
from ..utils import log

# per-tree numeric arrays saved verbatim (HostTree field -> npz entry)
_TREE_FIELDS = ("split_feature", "threshold", "threshold_bin",
                "decision_type", "left_child", "right_child", "split_gain",
                "internal_value", "internal_weight", "internal_count",
                "leaf_value", "leaf_weight", "leaf_count", "leaf_depth")

_SANITY_KEYS = ("objective", "num_class", "tree_learner", "num_leaves",
                "learning_rate", "max_bin", "bagging_seed", "bagging_freq",
                "bagging_fraction", "feature_fraction",
                "feature_fraction_seed", "seed")


def _fetch_rows(gbdt, arr) -> np.ndarray:
    """Device score matrix -> host numpy; under multi-process a sharded
    carry yields this rank's [k, block] column block."""
    mp = getattr(gbdt, "mp", None)
    if mp is not None and not getattr(arr, "is_fully_addressable", True):
        return np.asarray(mp.local_block(arr, axis=1))
    return np.asarray(arr)


def trees_to_arrays(models: List[HostTree]) -> Tuple[List[Dict], Dict]:
    """(per-tree JSON meta, npz arrays) for a model list — shared by the
    checkpoint capture and the recovery re-sync blob."""
    meta: List[Dict] = []
    arrays: Dict[str, np.ndarray] = {}
    for i, ht in enumerate(models):
        m: Dict[str, Any] = {
            "num_leaves": int(ht.num_leaves),
            "shrinkage": float(ht.shrinkage),
            "cat_boundaries": [int(x) for x in ht.cat_boundaries],
            "cat_threshold": [int(x) for x in ht.cat_threshold],
        }
        if ht.is_linear:
            m["is_linear"] = True
            m["leaf_const"] = [float(x) for x in np.asarray(ht.leaf_const)]
            m["leaf_features"] = [[int(f) for f in fs]
                                  for fs in ht.leaf_features]
            m["leaf_coeff"] = [[float(c) for c in cs]
                               for cs in ht.leaf_coeff]
        meta.append(m)
        for f in _TREE_FIELDS:
            arrays[f"t{i}_{f}"] = np.array(getattr(ht, f))
    return meta, arrays


def trees_from_arrays(meta: List[Dict], arrays) -> List[HostTree]:
    models: List[HostTree] = []
    for i, m in enumerate(meta):
        ht = HostTree(int(m["num_leaves"]),
                      shrinkage=float(m.get("shrinkage", 1.0)))
        for f in _TREE_FIELDS:
            setattr(ht, f, np.array(arrays[f"t{i}_{f}"]))
        ht.cat_boundaries = [int(x) for x in m.get("cat_boundaries", [0])]
        ht.cat_threshold = [int(x) for x in m.get("cat_threshold", [])]
        if m.get("is_linear"):
            ht.is_linear = True
            ht.leaf_const = np.asarray(m.get("leaf_const", []), np.float64)
            ht.leaf_features = [list(fs) for fs in m.get("leaf_features",
                                                         [])]
            ht.leaf_coeff = [list(cs) for cs in m.get("leaf_coeff", [])]
        models.append(ht)
    return models


# ------------------------------------------------------------- capture
def capture(gbdt) -> Tuple[Dict[str, Any], Dict[str, np.ndarray]]:
    """Snapshot the driver's resumable state as (JSON payload, arrays).
    Called at a consistency boundary (pending queue drained)."""
    import jax
    tel = gbdt.telemetry
    cfg = gbdt.config
    k = gbdt.num_tree_per_iteration
    meta, arrays = trees_to_arrays(gbdt.models)
    arrays["scores"] = _fetch_rows(gbdt, gbdt.scores)
    for vi, vs in enumerate(gbdt.valid_scores):
        arrays[f"vscore{vi}"] = _fetch_rows(gbdt, vs)
    arrays["bag_stream_state"] = np.array(gbdt.bag_streams.state)
    bag = {"is_bagging": bool(gbdt.is_bagging),
           "bag_cnt": int(gbdt.bag_cnt)}
    if gbdt.is_bagging:
        host_w = getattr(gbdt, "_bag_weight_host", None)
        arrays["bag_weight"] = (np.asarray(host_w) if host_w is not None
                                else np.asarray(gbdt.bag_weight))
    cache = getattr(gbdt, "_bag_round_cache", None) or {}
    bag["cache_keys"] = sorted(int(key) for key in cache)
    for j, key in enumerate(bag["cache_keys"]):
        arrays[f"bag_cache{j}"] = np.asarray(cache[key], bool)
    if getattr(gbdt, "use_screening", False) \
            and getattr(gbdt, "_gain_ema_dev", None) is not None:
        # EMA-FS gain-screening state (tpu_gain_screening): the
        # per-feature gain EMA is part of the resumable training state —
        # without it a resumed run would re-warm the mask and diverge
        # from the uninterrupted run's feature screening
        arrays["gain_ema"] = np.asarray(gbdt._gain_ema_dev, np.float32)
    extra_payload, extra_arrays = gbdt._capture_boosting_extra()
    arrays.update(extra_arrays)
    extra_cb = getattr(gbdt, "_ckpt_extra", None)
    engine_extra: Dict[str, Any] = {}
    if extra_cb is not None:
        try:
            engine_extra = extra_cb() or {}
        except Exception as e:
            log.warning("checkpoint extra-state capture failed: %s", e)
    counters: Dict[str, float] = {}
    if tel.enabled:
        counters = tel.snapshot()["counters"]
    payload = {
        "schema": 1,
        "iteration": int(gbdt.iter),
        "num_init_iteration": int(gbdt.num_init_iteration),
        "boosting": gbdt.name,
        "rank": int(tel.rank),
        "world": int(jax.process_count()),
        "k": int(k),
        "n_trees": len(gbdt.models),
        "n_valid": len(gbdt.valid_scores),
        # rank=-1: never salt the manifest hash with the health fault
        # injection — the manifest must describe the REAL model state
        "model_hash": model_state_hash(gbdt.models, rank=-1),
        "shrinkage_rate": float(gbdt.shrinkage_rate),
        "trees_meta": meta,
        "bag": bag,
        "feat_rng_x": int(gbdt.feat_rng.x),
        "best": [[ds, name, float(gbdt.best_score[(ds, name)]),
                  int(gbdt.best_iter.get((ds, name), 0))]
                 for (ds, name) in sorted(gbdt.best_score)],
        "boosting_extra": extra_payload,
        "engine_extra": engine_extra,
        "telemetry_counters": counters,
        "sanity": {key: getattr(cfg, key, None) for key in _SANITY_KEYS},
        # drift & lineage plane: the training DataProfile and provenance
        # record ride every checkpoint manifest, so a booster resurrected
        # from a checkpoint (rollover source) carries its training
        # distribution and lineage exactly like a model-file booster
        # (copies: the writer serialises on its own thread while the
        # finalize epilogue adds "score" to the live dicts)
        "data_profile": copy.deepcopy(getattr(gbdt, "data_profile", None)),
        "provenance": copy.deepcopy(getattr(gbdt, "provenance", None)),
    }
    return payload, arrays


# ------------------------------------------------------------- restore
def restore(gbdt, payload: Dict[str, Any], arrays) -> int:
    """Rebuild the driver's training state from a checkpoint snapshot;
    returns the restored iteration. Precondition: the booster was just
    constructed against the SAME dataset/params and every valid set was
    added (engine.train enforces the order)."""
    import jax
    import jax.numpy as jnp
    if payload.get("schema") != 1:
        log.fatal("unsupported checkpoint schema %r",
                  payload.get("schema"))
    if payload.get("boosting") != gbdt.name:
        log.fatal("checkpoint was written by boosting=%s; this run is %s",
                  payload.get("boosting"), gbdt.name)
    if int(payload.get("k", 0)) != gbdt.num_tree_per_iteration:
        log.fatal("checkpoint has %s trees/iteration, run has %d",
                  payload.get("k"), gbdt.num_tree_per_iteration)
    if int(payload.get("world", 1)) != jax.process_count():
        log.fatal("checkpoint was written by a %s-process run; this run "
                  "spans %d processes (score shards are rank-local)",
                  payload.get("world"), jax.process_count())
    if int(payload.get("n_valid", 0)) != len(gbdt.valid_scores):
        log.fatal("checkpoint carries %s valid sets, run has %d — add "
                  "the same valid sets before resuming",
                  payload.get("n_valid"), len(gbdt.valid_scores))
    sanity = payload.get("sanity") or {}
    cfg = gbdt.config
    drift = {key: (sanity.get(key), getattr(cfg, key, None))
             for key in _SANITY_KEYS
             if key in sanity and sanity[key] != getattr(cfg, key, None)}
    if drift:
        log.warning("resume with changed parameters (bit-identity to an "
                    "uninterrupted run is off): %s",
                    {key: f"{a!r}->{b!r}" for key, (a, b) in drift.items()})

    gbdt.drain_pending()
    models = trees_from_arrays(payload["trees_meta"], arrays)
    want = payload.get("model_hash", "")
    got = model_state_hash(models, rank=-1)
    if want and got != want:
        log.fatal("restored model hash %s does not match the manifest's "
                  "%s — torn or mismatched checkpoint", got[:16],
                  want[:16])
    gbdt.models[:] = models
    gbdt.device_trees[:] = [gbdt._device_tree_for_resume(ht)
                            for ht in models]
    gbdt.iter = int(payload["iteration"])
    gbdt.num_init_iteration = int(payload.get("num_init_iteration", 0))
    gbdt.shrinkage_rate = float(payload.get("shrinkage_rate",
                                            gbdt.shrinkage_rate))

    mp = getattr(gbdt, "mp", None)
    scores = np.asarray(arrays["scores"], np.float32)
    gbdt.scores = (mp.shard_local_cols(scores) if mp is not None
                   else jnp.asarray(scores))
    for vi in range(len(gbdt.valid_scores)):
        gbdt.valid_scores[vi] = jnp.asarray(
            np.asarray(arrays[f"vscore{vi}"], np.float32))

    gbdt.bag_streams.state = np.asarray(arrays["bag_stream_state"],
                                        np.uint32)
    bag = payload.get("bag") or {}
    gbdt.bag_cnt = int(bag.get("bag_cnt", gbdt.bag_cnt))
    if bag.get("is_bagging") and "bag_weight" in arrays:
        w = np.asarray(arrays["bag_weight"], np.float32)
        if mp is not None:
            gbdt._bag_weight_host = w
            gbdt.bag_weight = mp.shard_full(w)
        else:
            gbdt.bag_weight = jnp.asarray(w)
    cache: Dict[int, np.ndarray] = {}
    for j, key in enumerate(bag.get("cache_keys", [])):
        cache[int(key)] = np.asarray(arrays[f"bag_cache{j}"], bool)
    gbdt._bag_round_cache = cache or None
    gbdt.feat_rng.x = int(payload.get("feat_rng_x", gbdt.feat_rng.x))
    if "gain_ema" in arrays and getattr(gbdt, "use_screening", False):
        gbdt._gain_ema_dev = jnp.asarray(
            np.asarray(arrays["gain_ema"], np.float32))
        gbdt._screen_mask_cache = None
        gbdt._iter_gain_acc = None

    gbdt.best_score.clear()
    gbdt.best_iter.clear()
    for ds, name, score, it in payload.get("best", []):
        gbdt.best_score[(ds, name)] = float(score)
        gbdt.best_iter[(ds, name)] = int(it)

    gbdt._restore_boosting_extra(payload.get("boosting_extra") or {},
                                 arrays)
    gbdt.telemetry.restore_counters(payload.get("telemetry_counters")
                                    or {})
    # transient driver state: a fresh run continues from here
    gbdt._stopped_early = False
    gbdt._es_finished = False
    gbdt._es_carry = None
    gbdt._last_ckpt_iter = gbdt.iter
    # lineage: the resumed run descends from this checkpoint — chain the
    # parent hash into the (freshly built) provenance record
    gbdt._parent_ckpt_hash = str(want or got)
    prov = getattr(gbdt, "provenance", None)
    if prov is not None:
        prov["parent_checkpoint"] = gbdt._parent_ckpt_hash
    gbdt.telemetry.event("resumed", iteration=gbdt.iter,
                         trees=len(models),
                         model_hash=got[:16])
    log.info("resumed training at iteration %d (%d trees, hash %s)",
             gbdt.iter, len(models), got[:16])
    return gbdt.iter


def synthesize_es_carry(gbdt, es_state: Dict[str, Any]) -> bool:
    """Rebuild the megastep scan's device early-stop carry from a
    restored early_stopping-callback state. The carry is fully derivable
    from the callback's host state (same f32 values, same strict
    compares — metric/traced.py mirrors the callback's state machine),
    so checkpoints stay driver-agnostic: a sync-driver checkpoint
    resumes onto the megastep and vice versa."""
    import jax.numpy as jnp
    plan = gbdt._traced_plan
    if plan is None or not es_state.get("inited"):
        return False
    slots = plan.slots
    best_scores = es_state.get("best_score") or []
    best_iters = es_state.get("best_iter") or []
    seen = es_state.get("seen") or []
    if len(best_scores) != len(slots):
        log.warning("restored early-stop state covers %d slots, the "
                    "traced plan has %d; device carry starts fresh",
                    len(best_scores), len(slots))
        return False
    sign = np.asarray([1.0 if bigger else -1.0
                       for (_, _, bigger) in slots], np.float32)
    best = np.full(len(slots), -np.inf, np.float32)
    bround = np.full(len(slots), -1, np.int32)
    for i in range(len(slots)):
        if i < len(seen) and seen[i]:
            best[i] = np.float32(best_scores[i]) * sign[i]
            bround[i] = np.int32(best_iters[i])
    gbdt._es_carry = (jnp.asarray(best), jnp.asarray(bround),
                      jnp.zeros((), bool),
                      jnp.full((), -1, jnp.int32))
    return True


# -------------------------------------------------- booster-level entry
def resolve_checkpoint(path: str, world: int) -> str:
    """Accept either a concrete ``ckpt_*`` directory or a checkpoint
    root (selects the newest complete hash-consistent one)."""
    import os

    from .checkpoint import checkpoint_manifests, select_checkpoint
    if not os.path.isdir(path):
        log.fatal("resume path %r is not a directory", path)
    if checkpoint_manifests(path, world) is not None:
        return path
    sel = select_checkpoint(path, world)
    if sel is None:
        log.fatal("no complete %d-rank checkpoint under %r "
                  "(torn or missing manifests)", world, path)
    return sel


def restore_into_booster(booster, path: str) -> Dict[str, Any]:
    """Load this rank's slice of a checkpoint and restore the booster's
    driver; returns the payload (the engine applies callback state and
    the ES carry from payload['engine_extra'])."""
    import jax

    from .checkpoint import load_rank
    gbdt = booster._gbdt
    if gbdt is None:
        log.fatal("resume requires a booster constructed with a train_set")
    world = jax.process_count()
    cdir = resolve_checkpoint(str(path), world)
    payload, arrays = load_rank(cdir, gbdt.telemetry.rank)
    restore(gbdt, payload, arrays)
    booster.best_iteration = -1
    booster._model_version += 1
    return payload


def booster_from_checkpoint(path: str, rank: int = 0):
    """Standalone (prediction/serving-only) ``Booster`` from a
    resilience checkpoint — the train→serve rollover source.

    Accepts a concrete ``ckpt_<n>`` directory or a checkpoint root
    (newest checkpoint with a valid ``rank{rank}`` manifest; the model
    is replicated across ranks, so rank 0's trees ARE the full model).
    Trees restore f64-binary-exact (:func:`trees_from_arrays`) and are
    hash-verified against the manifest; objective / num_class /
    averaging come from the checkpoint's sanity block so
    finalize-prediction semantics match the training run.  No training
    dataset is attached — serving packs it through the raw device
    predictor, exactly like a model-file booster.
    """
    import os

    from ..basic import Booster
    from ..objective import create_objective_from_string
    from .checkpoint import _read_manifest, list_checkpoints, load_rank

    cdir = str(path)

    def _has_rank(d: str) -> bool:
        return _read_manifest(
            os.path.join(d, f"rank{rank}.json")) is not None

    if not (os.path.isdir(cdir) and _has_rank(cdir)):
        sel = next((p for _, p in list_checkpoints(cdir)
                    if _has_rank(p)), None) if os.path.isdir(cdir) \
            else None
        if sel is None:
            raise FileNotFoundError(
                f"no checkpoint with a valid rank{rank} manifest under "
                f"{path!r}")
        cdir = sel
    payload, arrays = load_rank(cdir, rank)
    models = trees_from_arrays(payload["trees_meta"], arrays)
    want = payload.get("model_hash", "")
    got = model_state_hash(models, rank=-1)
    if want and got != want:
        raise ValueError(
            f"checkpoint {cdir!r}: restored model hash {got[:16]} does "
            f"not match the manifest's {want[:16]} — torn or mismatched "
            "checkpoint")
    sanity = payload.get("sanity") or {}
    b = Booster()
    b.models = models
    b.num_tree_per_iteration = max(1, int(payload.get("k", 1)))
    b.num_class = max(1, int(sanity.get("num_class") or 1))
    # rf averages its trees; every other boosting mode sums
    b.average_output = payload.get("boosting") == "rf"
    max_feat = 0
    for ht in models:
        sf = np.asarray(ht.split_feature)
        if sf.size:
            max_feat = max(max_feat, int(sf.max()))
    b.max_feature_idx = max_feat
    obj = str(sanity.get("objective") or "none")
    if b.num_class > 1 and "num_class" not in obj:
        obj = f"{obj} num_class:{b.num_class}"
    b._objective_str = obj
    b.objective = create_objective_from_string(obj)
    b.data_profile = payload.get("data_profile")
    b.provenance = payload.get("provenance")
    if b.data_profile and "score" not in b.data_profile:
        # the finalize epilogue (Booster._capture_score_profile), from the
        # scores this checkpoint holds
        add_score_distribution(b.data_profile, arrays["scores"])
        if b.provenance is not None:
            b.provenance["profile_digest"] = profile_digest(b.data_profile)
    b.best_iteration = -1
    b._model_version += 1
    log.info("rollover source: checkpoint %s (iteration %s, %d trees, "
             "hash %s)", cdir, payload.get("iteration"), len(models),
             got[:16])
    return b


def callback_states(callbacks: List) -> List[Dict[str, Any]]:
    """Serializable state of every stateful callback (those exposing
    ``_cb_state``), tagged by kind + position."""
    out = []
    for pos, cb in enumerate(callbacks):
        state_fn = getattr(cb, "_cb_state", None)
        if state_fn is None:
            continue
        try:
            st = state_fn()
        except Exception as e:
            log.warning("callback state capture failed: %s", e)
            continue
        out.append({"kind": getattr(cb, "_megastep_replay",
                                    type(cb).__name__),
                    "pos": pos, "state": st})
    return out


def restore_callback_states(callbacks: List, saved: List[Dict[str, Any]],
                            env) -> Optional[Dict[str, Any]]:
    """Feed saved states back into matching callbacks (by kind, in
    order); returns the restored early_stopping state (for the ES-carry
    synthesis) when one was present."""
    es_state = None
    by_kind: Dict[str, List[Dict]] = {}
    for ent in saved or []:
        by_kind.setdefault(ent.get("kind", ""), []).append(ent)
    for cb in callbacks:
        kind = getattr(cb, "_megastep_replay", None)
        restore_fn = getattr(cb, "_cb_restore", None)
        if restore_fn is None or kind is None:
            continue
        pool = by_kind.get(kind)
        if not pool:
            continue
        ent = pool.pop(0)
        try:
            restore_fn(ent["state"], env)
        except Exception as e:
            if kind == "early_stopping":
                # a broken ES restore (e.g. the slot count changed
                # across the resume) silently changes the stopping
                # decision — the one thing the resume API promises not
                # to do. Fail loudly instead of training on.
                log.fatal("early-stopping state restore failed: %s — "
                          "resume with the same valid sets/metrics the "
                          "interrupted run used, or drop the "
                          "early_stopping callback", e)
            log.warning("callback state restore failed (%s): %s", kind, e)
            continue
        if kind == "early_stopping":
            es_state = ent["state"]
    return es_state


def eval_list_from_payload(payload: Dict[str, Any]) -> List[tuple]:
    ev = (payload.get("engine_extra") or {}).get("eval_list") or []
    return [tuple(t) for t in ev]


def dumps_compact(obj: Any) -> str:
    return json.dumps(obj, separators=(",", ":"), default=str)
