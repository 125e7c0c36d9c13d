"""On-chip profiling entry points, consolidated.

Three modes behind one documented wrapper (iter/micro used to live in
``profile_iter.py`` / ``profile_micro.py``, which drifted apart):

    # per-phase wall timing of one fused-engine boosting iteration,
    # driven through the product path on the attached chip
    BENCH_ROWS=2000000 python scripts/profile.py iter

    # micro-benchmarks of the primitives that bound GBDT training
    # (matmul/HBM/gather/sort/cumsum/Pallas histogram), each chained
    # inside ONE jit so the measurement is device throughput, not
    # dispatch latency
    python scripts/profile.py micro

    # parse a captured jax.profiler trace dir (a profile_dir config
    # window or a POST /profile capture) via obs/kernelstats.py and
    # print the top-K kernels by measured device time, joined to
    # their cost-ledger signatures when --telemetry points at the
    # run's JSONL — no TensorBoard needed (docs/Observability.md §15)
    python scripts/profile.py summarize /tmp/prof \
        [--telemetry run.jsonl] [--top 10] [--json]

For profiling a LIVE training job, the capture side is neither bench:
set ``metrics_port=<p>`` and ``POST /profile?iters=N`` against the
running process — the driver captures a bounded ``jax.profiler`` trace
at its next drain boundary without restarting the job
(docs/Observability.md §12), then ``summarize`` reads it back.
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ------------------------------------------------------------ iter mode
def _timed(label, fn, *a, **k):
    import jax
    t0 = time.perf_counter()
    out = fn(*a, **k)
    for x in jax.tree_util.tree_leaves(out):
        if hasattr(x, "block_until_ready"):
            x.block_until_ready()
    dt = time.perf_counter() - t0
    print(f"  {label:34s} {dt*1e3:9.1f} ms")
    return out


def main_iter() -> None:
    """Per-phase timing of one fused-engine boosting iteration on the
    attached chip (BENCH_ROWS scales the dataset)."""
    import numpy as np

    import jax.numpy as jnp

    import lightgbm_tpu as lgb

    n = int(os.environ.get("BENCH_ROWS", 2_000_000))
    rng = np.random.RandomState(0)
    X = rng.rand(n, 28).astype(np.float32)
    w = rng.randn(28).astype(np.float32)
    y = (X @ w + 0.5 * rng.randn(n) > 0).astype(np.float32)
    params = {"objective": "binary", "max_bin": 63, "num_leaves": 255,
              "learning_rate": 0.1, "min_data_in_leaf": 1,
              "min_sum_hessian_in_leaf": 1e-3, "verbose": -1,
              "metric": "None", "tpu_engine": "fused"}
    ds = lgb.Dataset(X, label=y, params={"max_bin": 63, "verbose": -1})
    booster = lgb.Booster(params=params, train_set=ds)
    for _ in range(3):
        booster.update()  # warm all compiles

    g = booster._gbdt
    print(f"rows={n}")
    for rep in range(2):
        print(f"--- iter {rep}")
        t0_all = time.perf_counter()
        grad, hess = _timed("get_gradients", g._get_gradients)
        gh = _timed("gh stack", lambda: jnp.stack(
            [grad[0] * g.bag_weight, hess[0] * g.bag_weight,
             g.bag_weight], axis=1))
        from lightgbm_tpu.ops.fused_level import pack_gh, table_lookup
        fm = g._feature_mask()
        pad = g.fused_Rp - g.num_data
        gh_T = _timed("pack_gh+pad", lambda: pack_gh(
            jnp.pad(gh[:, 0], (0, pad)), jnp.pad(gh[:, 1], (0, pad)),
            jnp.pad(gh[:, 2], (0, pad)), g.fused_nch))
        fm_pad = jnp.zeros((g.fused_f_oh,), bool).at[:fm.shape[0]].set(fm)
        from lightgbm_tpu.models.frontier2 import grow_tree_fused
        tree, row_leaf = _timed("grow_tree_fused", lambda: grow_tree_fused(
            g.fused_bins_T, gh_T, g.fused_meta, fm_pad, g.params,
            g.max_leaves, g.fused_Bp, g.fused_f_oh, num_rows=g.num_data,
            nch=g.fused_nch, max_depth=int(g.config.max_depth),
            extra_levels=int(g.config.tpu_extra_levels),
            has_cat=g.has_cat, use_mono_bounds=g.use_mono_bounds,
            use_node_masks=g.use_node_masks,
            node_masks=g._node_masks_padded(),
            interpret=g.fused_interpret))
        _timed("int(num_leaves)", lambda: int(tree.num_leaves))
        ht, sf = _timed("to_host_tree", g._to_host_tree, tree,
                        g.shrinkage_rate)
        ht.apply_shrinkage(g.shrinkage_rate)
        lv_dev = jnp.asarray(ht.leaf_value, jnp.float32)
        delta = _timed("table_lookup", lambda: table_lookup(
            row_leaf[:g.num_data][None, :], lv_dev)[0])
        _timed("score add", lambda: g.scores.at[0].add(delta))
        print(f"  {'TOTAL':34s} "
              f"{(time.perf_counter()-t0_all)*1e3:9.1f} ms")


# ----------------------------------------------------------- micro mode
def _timeit(fn, *args, reps=3, warmup=1):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _chain(body, n):
    """Run body n times sequentially inside one jit (data-dependent)."""
    import jax

    @jax.jit
    def run(*args):
        def step(i, carry):
            return body(i, carry, *args[1:])
        return jax.lax.fori_loop(0, n, step, args[0])
    return run


def main_micro() -> None:
    """Micro-benchmarks of the primitives that bound GBDT training on
    TPU, each chained N times inside ONE jit-compiled loop."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    R = 2_000_000
    Fp = 32
    N = 10
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, 63, size=(R, Fp)).astype(np.int32))
    bins_u8 = jnp.asarray(np.asarray(bins).astype(np.uint8))
    perm = jnp.asarray(rng.permutation(R).astype(np.int32))
    slot = jnp.asarray(rng.randint(0, 64, size=R).astype(np.int32))

    results = {}

    # 0. raw MXU throughput (chained, data-dependent)
    a = jnp.asarray(rng.randn(4096, 4096).astype(np.float32)).astype(
        jnp.bfloat16)
    f = _chain(lambda i, x, a: (x @ a), N)
    t = _timeit(f, a, a) / N
    results["matmul_4096_bf16_tflops"] = 2 * 4096**3 / t / 1e12

    # 1. HBM r/w bandwidth (chained adds)
    big = jnp.zeros((R, Fp), jnp.float32)
    f = _chain(lambda i, x: x + 1.0, N)
    t = _timeit(f, big) / N
    results["hbm_rw_f32_GBps"] = 2 * R * Fp * 4 / t / 1e9

    # 2. random row gather [R, Fp] uint8 (index fed by previous gather
    # so the chain cannot be elided)
    f = _chain(lambda i, p, x: (p + x[p][:, 0].astype(jnp.int32)) % R, N)
    t = _timeit(f, perm, bins_u8) / N
    results["row_gather_u8_ns_per_row"] = t / R * 1e9
    t = _timeit(f, perm, bins) / N
    results["row_gather_i32_ns_per_row"] = t / R * 1e9

    # 2b. 1-D gather / scatter
    f = _chain(lambda i, p, x: (p + x[p]) % R, N)
    t = _timeit(f, perm, slot) / N
    results["gather_1d_ns_per_elem"] = t / R * 1e9
    f = _chain(lambda i, p, x: (p + jnp.zeros_like(x).at[p].set(x)) % R,
               N)
    t = _timeit(f, perm, slot) / N
    results["scatter_1d_unique_ns_per_elem"] = t / R * 1e9

    # 3. sort (key,payload)
    f = _chain(lambda i, k, v: jax.lax.sort(((k * 7919 + 13) % R, v),
                                            num_keys=1)[0], N)
    t = _timeit(f, slot, perm) / N
    results["sort_kv_2M_ms"] = t * 1e3

    # 4. cumsum
    f = _chain(lambda i, x: jnp.cumsum(x) % 1000, N)
    t = _timeit(f, slot) / N
    results["cumsum_2M_ms"] = t * 1e3

    for k, v in results.items():
        print(f"{k:36s} {v if isinstance(v, str) else round(v, 3)}")


# ------------------------------------------------------- summarize mode
def main_summarize(argv) -> int:
    """Parse a profile dir (obs/kernelstats.py) and print the top-K
    kernels and per-executable measured device times, joined to
    cost-ledger signatures when a telemetry JSONL is given.  Host-side
    stdlib parsing only — runs anywhere, no TensorBoard."""
    import argparse
    import json

    from lightgbm_tpu.obs import kernelstats

    ap = argparse.ArgumentParser(
        prog="profile.py summarize",
        description="summarize a jax.profiler trace dir")
    ap.add_argument("dir", help="profile dir (the profile_dir config "
                                "window or POST /profile target)")
    ap.add_argument("--telemetry", default="",
                    help="telemetry_out JSONL of the same run — joins "
                         "kernels to cost/compile signatures")
    ap.add_argument("--top", type=int, default=10,
                    help="top-K kernels/executables to print")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the full roofline record as JSON")
    args = ap.parse_args(argv)

    cost = compiles = None
    if args.telemetry:
        events = []
        with open(args.telemetry) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    pass
        cost, compiles = kernelstats.cost_entries_from_events(events)
    roof = kernelstats.roofline_from_dir(args.dir, cost_entries=cost,
                                         compile_entries=compiles,
                                         top=args.top)
    if args.as_json:
        print(json.dumps(roof, indent=1, sort_keys=True, default=str))
        return 0
    print(f"trace dir: {args.dir}")
    print(f"  files parsed: {roof['parsed_files']}/{roof['trace_files']}"
          f"  ({roof['trace_bytes']} bytes, "
          f"{roof['parse_errors']} errors)")
    print(f"  anchor dispatches: {roof['anchor_dispatches']}  "
          f"join coverage: {roof['join_coverage']:.3f}  "
          f"device time: {roof['total_device_time_us']:.1f} us "
          f"(+{roof['unattributed_time_us']:.1f} us unattributed)")
    for err in roof.get("errors", []):
        print(f"  ! {err}")
    if roof["executables"]:
        print("executables (by measured device time):")
    for ex in roof["executables"][:args.top]:
        sig = ex.get("signature") or f"<unjoined:{ex['kind']}>"
        per = ex.get("device_time_us_per_dispatch")
        frac = ex.get("measured_fraction")
        line = (f"  {sig:48s} {ex['device_time_us']:10.1f} us  "
                f"x{ex['dispatches']}")
        if per is not None:
            line += f"  {per:9.1f} us/disp"
        if frac is not None:
            line += f"  frac={frac:.3f}"
        if ex.get("achieved_flops_per_s") is not None:
            line += (f"  {ex['achieved_flops_per_s']:.3e} flop/s"
                     f"  {ex['achieved_bytes_per_s']:.3e} B/s")
        print(line)
        for k in ex.get("top_kernels", [])[:3]:
            print(f"      {k['name']:44s} {k['time_us']:10.1f} us  "
                  f"x{k['count']}")
    if roof["kernels"]:
        print("top kernels (all lanes):")
    for k in roof["kernels"][:args.top]:
        print(f"  {k['name']:48s} {k['time_us']:10.1f} us  "
              f"x{k['count']}")
    if not args.telemetry:
        print("(no --telemetry JSONL given: executables stay unjoined; "
              "pass the run's telemetry_out file to join signatures)")
    return 0


def main() -> int:
    mode = sys.argv[1] if len(sys.argv) > 1 else ""
    if mode == "iter":
        main_iter()
    elif mode == "micro":
        main_micro()
    elif mode == "summarize":
        return main_summarize(sys.argv[2:])
    else:
        print(__doc__)
        print("usage: python scripts/profile.py "
              "{iter|micro|summarize <dir>}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
