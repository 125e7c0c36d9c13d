#!/usr/bin/env python3
"""One run of one cell of the benchmark, in a new process.

  python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json`` (benchmark/README.md). The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device`` and, traced, ``breakdown``. Everything else
goes to standard error. Without a TPU (or with fewer chips than the cell
asks for) it exits non-zero and prints no result; ``--manifest`` with a
rehearsal manifest is the one way to run on the CPU, and such a run
prints no device metric.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from harness import cells, context, monitor, output  # noqa: E402


def say(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--manifest", default="",
                    help="a rehearsal manifest (CPU only; tests)")
    args = ap.parse_args()
    manifest = cells.load_manifest(args.manifest)
    rehearsal = bool(manifest.get("rehearsal", False))
    found = cells.find_cell(manifest, args.workload)
    chips = int(found["cell"]["chips"])

    # the system under test: this checkout's package, nothing installed
    sys.path.insert(0, cells.ROOT)
    import lightgbm_tpu  # noqa: F401
    from lightgbm_tpu.utils.platform import compilation_cache_dir
    import jax
    # <checkout>/.jax_cache unless JAX_COMPILATION_CACHE_DIR says otherwise
    cache_dir = compilation_cache_dir()
    # keep every program, however quick to compile, so that only the first
    # run of a cell in a checkout compiles anything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compile_log = monitor.CompileLog()
    t0 = time.time()
    devices = jax.devices()
    backend_init_s = time.time() - t0
    platform = devices[0].platform
    if platform != "tpu" and not rehearsal:
        sys.exit(f"benchmark: needs a TPU; JAX found platform "
                 f"{platform!r} ({devices[0].device_kind} x {len(devices)}). "
                 "Nothing was run.")
    if len(devices) < chips:
        sys.exit(f"benchmark: {args.workload} needs {chips} chips; JAX "
                 f"found {len(devices)}. Nothing was run.")
    say(f"{args.workload} seed {args.seed} on {platform} "
        f"{devices[0].device_kind} x {len(devices)}; compile cache "
        f"{cache_dir}")

    scratch = os.path.join(cells.BENCH, ".cache", args.workload)
    shutil.rmtree(scratch, ignore_errors=True)
    os.makedirs(scratch)
    run = context.Run(
        cell=found["cell"], config=found["config"],
        traffic=found["traffic"], seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearsal=rehearsal, t_start=T_START,
        devices=devices,
        compile_log=compile_log, scratch=scratch)
    run.phases["backend_init"] = backend_init_s
    kind = cells.load_module("kinds", found["traffic"]["kind"])
    with contextlib.redirect_stdout(sys.stderr):
        result = kind.run(run)
        group = "per_layer" if run.trace else "end_to_end"
        listed = cells.metrics_of(manifest, group, args.workload)
        if run.trace:
            values = {}
            for m in listed:
                reader = cells.load_module("layers", m["name"])
                values[m["name"]] = reader.read(run)
        else:
            values = result["metrics"]
    for p in result["problems"]:
        say(f"NOT CORRECT: {p}")
    print(output.line(run, listed, values, result), flush=True)


if __name__ == "__main__":
    main()
