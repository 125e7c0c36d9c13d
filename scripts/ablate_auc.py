"""The traced AUC's parts on the attached chip (PR 38's step 0).

Times, at the validation sets' sizes (1,333,332 rows: the Higgs cells;
1,000,000: the categorical cell) with scores from the benchmark's own
generators (the Higgs margin ``(X - 0.5) @ w``, nearly all distinct; the
categorical margin, a sum of per-category effects):

  old                the AUC until PR 38 (a stable argsort, three row
                     gathers, two ``segment_sum``s = scatter-adds, two
                     full-length cumsums), and its parts alone:
  old.argsort        ``jnp.argsort(-score, stable=True)``
  old.gathers        ``pos[order]``, ``w[order]``, ``score[order]``
  old.segment_sums   the two ``segment_sum``s with ``num_segments = n``
  old.cumsums        the group ids' and the group positives' cumsums
  new.b<B>           ``metric._weighted_auc_jnp`` with the three scans in
                     blocks of B (``metric.AUC_SCAN_BLOCK`` set to B for
                     the trace); ``new.flat``: one block of the whole
                     length, i.e. the plain scans
  new.b<B>.w         the weighted form (one more sort operand)
  sort.<stable|unstable>.<2|3>   ``lax.sort`` by the negated score with 1
                     or 2 more operands
  scan.<sum|max|min>.<plain|B>   one scan alone

Each line: ms per call, the median of REPS jitted calls after one warm-up
(``block_until_ready`` around each), and for an AUC its distance from the
float64 host ``_weighted_auc``. One JSON line per timing on stdout and in
chiprun_out/ablate_auc/timings.jsonl.

Run: python scripts/ablate_auc.py    (ROWS=1333332,1000000 REPS=5
BLOCKS=2048,8192; ROWS=20000 rehearses on the CPU)
"""
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np
import jax
import jax.numpy as jnp

from benchmark.harness import data, data_cat
from lightgbm_tpu import metric
from lightgbm_tpu.metric import _weighted_auc, _weighted_auc_jnp
from lightgbm_tpu.ops.scan import blocked_scan


_PLAIN = {"sum": jax.lax.cumsum, "max": jax.lax.cummax,
          "min": jax.lax.cummin}


def _old_order(score):
    return jnp.argsort(-score, stable=True)


def _old_gathers(order, pos, w, score):
    return pos[order] * w[order], w[order], score[order]


def _old_groups(ss):
    new_group = jnp.concatenate([jnp.ones((1,), bool), ss[1:] != ss[:-1]])
    return jnp.cumsum(new_group.astype(jnp.int32)) - 1


def _old_segment_sums(sp, sw, gid):
    n = sp.shape[0]
    return (jax.ops.segment_sum(sp, gid, num_segments=n),
            jax.ops.segment_sum(sw, gid, num_segments=n))


def _old_auc(label, score, weight):
    """metric._weighted_auc_jnp as it was until PR 38."""
    pos = (label > 0).astype(jnp.float32)
    w = weight if weight is not None else jnp.ones_like(pos)
    sp, sw, ss = _old_gathers(_old_order(score), pos, w, score)
    g_pos, g_all = _old_segment_sums(sp, sw, _old_groups(ss))
    g_neg = g_all - g_pos
    cum_pos_before = jnp.concatenate(
        [jnp.zeros((1,), g_pos.dtype), jnp.cumsum(g_pos)[:-1]])
    s_area = jnp.sum(g_neg * (cum_pos_before + 0.5 * g_pos))
    total_pos = jnp.sum(sp)
    total_neg = jnp.sum(sw) - total_pos
    return jnp.where((total_pos <= 0) | (total_neg <= 0), 1.0,
                     s_area / (total_pos * total_neg))


def _time(fn, args, reps):
    out = jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ms), out


def _scores(source, rows):
    if source == "higgs":
        _, _, Xv, yv = data.make_data(3800001, 0, rows, 28)
        w = data.weights(28)
        s = (Xv - np.float32(0.5)) @ w
    else:
        _, _, Xv, yv = data_cat.make_data(3800002, 0, rows)
        s = data_cat.margin(Xv, data_cat.effects())
    return yv.astype(np.float32), s.astype(np.float32)


def main():
    rows_list = [int(r) for r in
                 os.environ.get("ROWS", "1333332,1000000").split(",")]
    reps = int(os.environ.get("REPS", 5))
    blocks = [int(b) for b in os.environ.get("BLOCKS", "2048,8192").split(",")]
    dev = jax.devices()[0]
    block0 = metric.AUC_SCAN_BLOCK
    out_dir = os.path.join("chiprun_out", "ablate_auc")
    os.makedirs(out_dir, exist_ok=True)
    sink = open(os.path.join(out_dir, "timings.jsonl"), "w")

    for rows, source in zip(rows_list, ["higgs", "cat"]):
        y_np, s_np = _scores(source, rows)
        w_np = np.random.default_rng(38).uniform(
            0.5, 1.5, rows).astype(np.float32)
        y, s, w = (jnp.asarray(a) for a in (y_np, s_np, w_np))
        host = _weighted_auc(y_np, s_np, None)
        host_w = _weighted_auc(y_np, s_np, w_np)
        distinct = int(np.unique(s_np).size)

        def say(variant, ms, auc=None, ref=None):
            rec = {"variant": variant, "ms": round(ms, 4), "rows": rows,
                   "scores": source, "distinct": distinct,
                   "device": dev.device_kind}
            if auc is not None:
                rec["auc"] = float(auc)
                rec["abs_diff"] = abs(float(auc) - ref)
            line = json.dumps(rec)
            print(line, flush=True)
            sink.write(line + "\n")
            sink.flush()

        def auc(variant, fn, args, ref):
            ms, out = _time(jax.jit(fn), args, reps)
            say(variant, ms, out, ref)

        def part(variant, fn, args):
            say(variant, _time(jax.jit(fn), args, reps)[0])

        auc("old", lambda y, s: _old_auc(y, s, None), (y, s), host)
        auc("old.w", _old_auc, (y, s, w), host_w)
        order = jax.jit(_old_order)(s)
        pos = (y > 0).astype(jnp.float32)
        ones = jnp.ones_like(pos)
        sp, sw, ss = jax.jit(_old_gathers)(order, pos, ones, s)
        gid = jax.jit(_old_groups)(ss)
        g_pos, _ = jax.jit(_old_segment_sums)(sp, sw, gid)
        part("old.argsort", _old_order, (s,))
        part("old.gathers", _old_gathers, (order, pos, ones, s))
        part("old.segment_sums", _old_segment_sums, (sp, sw, gid))
        part("old.cumsums", lambda ss, g: (_old_groups(ss), jnp.cumsum(g)),
             (ss, g_pos))

        for b in [rows] + blocks:
            # read when the AUC is traced, so each jit gets its own block
            metric.AUC_SCAN_BLOCK = b
            tag = "new.flat" if b == rows else f"new.b{b}"
            auc(tag, lambda y, s: _weighted_auc_jnp(y, s, None), (y, s), host)
            auc(f"{tag}.w", _weighted_auc_jnp, (y, s, w), host_w)
        metric.AUC_SCAN_BLOCK = block0

        ipos = (y > 0).astype(jnp.int32)
        for stable in (True, False):
            tag = "stable" if stable else "unstable"
            part(f"sort.{tag}.2", lambda s, p, st=stable: jax.lax.sort(
                (-s, p), num_keys=1, is_stable=st), (s, ipos))
            part(f"sort.{tag}.3", lambda s, p, w, st=stable: jax.lax.sort(
                (-s, p, w), num_keys=1, is_stable=st), (s, pos, w))

        for op in ("sum", "max", "min"):
            x = ipos if op == "sum" else jnp.asarray(
                np.random.default_rng(1).integers(0, rows, rows, np.int32))
            part(f"scan.{op}.plain", lambda x, op=op: _PLAIN[op](
                x, axis=0, reverse=op == "min"), (x,))
            for b in blocks:
                part(f"scan.{op}.{b}", lambda x, op=op, b=b: blocked_scan(
                    x, op, b, reverse=op == "min"), (x,))


if __name__ == "__main__":
    main()
