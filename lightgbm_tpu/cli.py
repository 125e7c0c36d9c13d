"""Command-line application.

Behavioral analog of the reference CLI (ref: src/main.cpp:11,
src/application/application.cpp:31): ``k=v`` arguments plus an optional
``config=<file>`` (one ``k=v`` per line, ``#`` comments; command-line
wins), tasks train / predict / refit-free convert paths:

    python -m lightgbm_tpu config=train.conf
    python -m lightgbm_tpu task=train data=train.csv valid=test.csv \\
        objective=binary num_iterations=100 output_model=model.txt
    python -m lightgbm_tpu task=predict data=test.csv \\
        input_model=model.txt output_result=preds.tsv

Observability flags (docs/Observability.md): ``telemetry_out=<path>``
streams structured JSONL telemetry (``telemetry_granularity=batch``,
the default, keeps the pipelined/megastep fast path and attributes time
per drained batch; ``iteration``/``section`` trade speed for finer
attribution), ``trace_out=<path>`` exports a Perfetto/Chrome-trace
timeline (one track per rank), ``health_check_period=N`` turns on the
cross-rank health auditor, ``profile_dir=<dir>`` captures a
jax.profiler trace of the training loop, and ``metrics_port=<p>``
serves the LIVE telemetry registry as an OpenMetrics/Prometheus
endpoint on ``http://127.0.0.1:<p>/metrics`` while the run is going
(rank r binds ``<p>+r`` under the multiproc launcher; rank 0 appends
the fleet counter view) — all ordinary config keys, so they work from
the command line and from config files alike. On a crash with
``telemetry_out`` set, the flight recorder dumps
``<telemetry_out>.crash.json``. ``compilation_cache_dir=<dir>`` makes
repeated CLI runs skip XLA recompiles (docs/Performance.md).

Resilience flags (docs/Reliability.md): ``checkpoint_dir=<dir>
checkpoint_period=N`` write async resumable checkpoints during
training, and ``task=train resume=<path>`` restores one (a concrete
``ckpt_<iteration>`` directory or the checkpoint_dir root — the newest
complete checkpoint is selected) and continues bit-identically to an
uninterrupted run.
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from .basic import Booster, Dataset
from .engine import train as _train
from .utils import log


def parse_args(argv: List[str]) -> Dict[str, str]:
    """k=v args + config file (ref: application.cpp:50-83 LoadParameters;
    command-line overrides the file)."""
    cli: Dict[str, str] = {}
    for a in argv:
        if "=" not in a:
            raise SystemExit(f"unrecognized argument: {a} (expected k=v)")
        k, v = a.split("=", 1)
        cli[k.strip()] = v.strip()
    params: Dict[str, str] = {}
    conf = cli.pop("config", None)
    if conf:
        with open(conf) as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if not line or "=" not in line:
                    continue
                k, v = line.split("=", 1)
                params[k.strip()] = v.strip()
    params.update(cli)
    return params


def run_train(params: Dict[str, str]) -> None:
    data = params.pop("data", None)
    if not data:
        raise SystemExit("task=train requires data=<file>")
    valid = params.pop("valid", params.pop("valid_data", ""))
    output_model = params.get("output_model", "LightGBM_model.txt")
    n_rounds = int(params.get("num_iterations",
                              params.get("num_boost_round", 100)))
    train_set = Dataset(data, params=dict(params))
    valid_sets = []
    valid_names = []
    for i, v in enumerate(p for p in valid.split(",") if p):
        valid_sets.append(Dataset(v, params=dict(params),
                                  reference=train_set))
        valid_names.append(f"valid_{i}")
    booster = _train(dict(params), train_set, num_boost_round=n_rounds,
                     valid_sets=valid_sets or None,
                     valid_names=valid_names or None)
    # the reference CLI saves ALL trees even after early stopping
    # (Application::Train -> SaveModelToFile(0, -1, ...)); -1 beats the
    # Python facade's best_iteration default
    booster.save_model(output_model, num_iteration=-1)
    log.info("Finished training; model saved to %s", output_model)
    tel_out = params.get("telemetry_out", params.get("telemetry_output"))
    if tel_out:
        log.info("Telemetry JSONL written to %s", tel_out)
    trace_out = params.get("trace_out", params.get("trace_output"))
    if trace_out:
        log.info("Load %s in chrome://tracing or ui.perfetto.dev",
                 trace_out)
    mp = getattr(getattr(booster, "_gbdt", None), "_metrics", None)
    if mp is not None and mp.url:
        log.info("OpenMetrics endpoint still live at %s (until this "
                 "process exits)", mp.url)


def run_predict(params: Dict[str, str]) -> None:
    data = params.pop("data", None)
    model = params.pop("input_model", None)
    if not data or not model:
        raise SystemExit("task=predict requires data=<file> and "
                         "input_model=<file>")
    out_path = params.pop("output_result", "LightGBM_predict_result.txt")
    booster = Booster(model_file=model)
    # predict-time keys (pred_device_min_work, pred_early_stop, ...)
    # ride the booster params so the path choice is CLI-controllable
    booster.params.update(params)
    from .io.file_loader import load_text_file
    # a prediction file may or may not carry the label column; default to
    # stripping column 0 only when the width says one extra column is
    # present (the reference requires the same layout as training data)
    lc = params.get("label_column")
    X, _, _ = load_text_file(data, label_column=-1 if lc is None else lc)
    n_feat = booster.num_feature()
    if lc is None and X.shape[1] == n_feat + 1:
        X = X[:, 1:]    # training-style file: first column is the label
    if X.shape[1] != n_feat:
        raise SystemExit(
            f"prediction data has {X.shape[1]} columns but the model "
            f"expects {n_feat} features (pass label_column=... if a "
            f"label column is present)")
    preds = booster.predict(
        X, raw_score=str(params.get("predict_raw_score",
                                    "false")).lower() == "true",
        pred_leaf=str(params.get("predict_leaf_index",
                                 "false")).lower() == "true",
        pred_contrib=str(params.get("predict_contrib",
                                    "false")).lower() == "true")
    np.savetxt(out_path, np.asarray(preds), fmt="%.9g", delimiter="\t")
    log.info("Finished prediction; results saved to %s", out_path)


def run_refit(params: Dict[str, str]) -> None:
    """(ref: application.cpp task=refit + gbdt.cpp:287 RefitTree)"""
    data = params.pop("data", None)
    model = params.pop("input_model", None)
    if not data or not model:
        raise SystemExit("task=refit requires data=<file> and "
                         "input_model=<file>")
    out_path = params.get("output_model", "LightGBM_model.txt")
    booster = Booster(model_file=model)
    from .io.file_loader import load_text_file
    X, y, _ = load_text_file(data,
                             label_column=params.get("label_column", 0))
    if y is None:
        raise SystemExit("refit data must carry a label column")
    decay = float(params.get("refit_decay_rate", 0.9))
    new_booster = booster.refit(X, y, decay_rate=decay)
    new_booster.save_model(out_path)
    log.info("Finished refit; model saved to %s", out_path)


def run_convert_model(params: Dict[str, str]) -> None:
    """(ref: application.cpp task=convert_model -> gbdt_model_text.cpp
    SaveModelToIfElse / tree.cpp:562 ToIfElse)"""
    model = params.pop("input_model", None)
    if not model:
        raise SystemExit("task=convert_model requires input_model=<file>")
    lang = params.get("convert_model_language", "cpp")
    if lang not in ("cpp", ""):
        raise SystemExit(f"convert_model_language={lang} is not supported "
                         "(cpp only, like the reference)")
    out_path = params.get("convert_model", "gbdt_prediction.cpp")
    from .io.model_io import model_to_if_else
    booster = Booster(model_file=model)
    with open(out_path, "w") as fh:
        fh.write(model_to_if_else(booster))
    log.info("Finished converting model; code saved to %s", out_path)


def run_save_binary(params: Dict[str, str]) -> None:
    """(ref: application.cpp:70-83 task=save_binary — load the training
    data, write the binary cache next to it, exit)

    Writes the sharded v2 cache artifact (docs/Data.md): versioned,
    SHA-256-manifested, mmap-able; ``Dataset(data="<file>.bin")`` /
    ``data=<file>.bin`` on a later run skips text parsing and binning
    entirely.  The build itself streams in bounded chunks
    (``two_round`` defaults ON here so host RSS stays O(chunk) — pass
    ``two_round=false`` to force the monolithic load;
    ``ingest_chunk_rows`` sizes the chunks)."""
    from .ingest.cache import CacheError
    data = params.pop("data", None)
    if not data:
        raise SystemExit("task=save_binary requires data=<file>")
    out = params.get("output_model", data + ".bin")
    params.setdefault("two_round", "true")
    if out == data + ".bin":
        # default destination == the auto-cache sidecar: stream packed
        # chunks STRAIGHT into the artifact (the parsed shard never
        # exists in RAM at once), fingerprinted for later auto-hits
        params.setdefault("save_binary", "true")
    ds = Dataset(data, params=dict(params))
    ds.construct()
    # the construct may already have produced the artifact at `out`
    # (streamed cache_out or the sidecar auto-write) — rewriting it
    # here would REPLACE the fingerprinted manifest with a source-less
    # one and turn every later save_binary auto-load into a miss
    stats = getattr(ds._inner, "ingest_stats", None) or {}
    already = (stats.get("cache_path") == out
               or getattr(ds._inner, "sidecar_cache_path", None) == out)
    if not already:
        try:
            ds._inner.save_binary(out)
        except CacheError as e:
            raise SystemExit(f"cannot save binary dataset: {e}")
    log.info("Finished saving binary dataset to %s", out)


def main(argv: List[str] = None) -> None:
    params = parse_args(sys.argv[1:] if argv is None else argv)
    task = params.pop("task", "train")
    if task == "train":
        run_train(params)
    elif task in ("predict", "prediction", "test"):
        run_predict(params)
    elif task == "refit":
        run_refit(params)
    elif task == "convert_model":
        run_convert_model(params)
    elif task == "save_binary":
        run_save_binary(params)
    else:
        raise SystemExit(f"unknown task: {task}")


if __name__ == "__main__":
    main()
