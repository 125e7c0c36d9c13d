"""Compile-probe the non-default kernel variants on the chip.

The main path (chip_smoke.py) runs level_pass/route_pass in their
default form only: bf16 hi/lo channels (nch=5), padded layout, no
feature mask. This probe compiles each OTHER variant once at the Higgs
layout (28 features x 64 bins, Sp in {8, 128}) and compares it with
interpret mode on the same device:

    level_pass  quant_bits=8 | quant_bits=16 | packed= | fmask= | nch=3
    route_pass  packed=

One JSON line per variant on stdout (ok, or the compiler's message);
exit code 1 if any variant failed. Needs a TPU, like chip_smoke.py.

    python3 scripts/probe_kernel_variants.py
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _variants(c):
    """(name, fn(interpret) -> tuple of arrays) per variant for one
    chip_smoke.kernel_case."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops import fused_level as fl
    from lightgbm_tpu.ops.layout import packed_feature_layout
    from lightgbm_tpu.ops.quantize import QNCH

    Sp, F_oh, Bp = c["Sp"], c["F_oh"], c["Bp"]
    g, h, w = (jnp.asarray(c[k]) for k in ("grad", "hess", "w"))
    kw = dict(num_slots=Sp, num_bins=Bp, f_oh=F_oh)

    def level(gh_T, W=c["W"], bins_T=c["bins_T"], fmask=None, **extra):
        return lambda interpret: fl.level_pass(
            bins_T, c["leaf_T"], gh_T, W, c["tbl"], fmask,
            interpret=interpret, **kw, **extra)

    out = []
    for bits in (8, 16):
        gh_q, _ = fl.pack_gh_quant(g, h, w, bits, np.uint32(7))
        out.append((f"level_pass quant_bits={bits}",
                    level(gh_q, nch=QNCH[bits], quant_bits=bits)))
    gh5 = fl.pack_gh(g, h, w, fl.NCH_PRECISE)
    pk = packed_feature_layout(c["meta"][0], 63, f_oh=F_oh)
    order = jnp.asarray(pk.feat_order, jnp.int32)
    bins_pk = jnp.zeros_like(c["bins_T"]).at[:len(pk.feat_order)].set(
        jnp.take(c["bins_T"], order, axis=0))
    W_pk = fl.pack_route_table(c["W"], pk)
    out.append(("level_pass packed",
                level(gh5, W=W_pk, bins_T=bins_pk, nch=fl.NCH_PRECISE,
                      packed=pk)))
    keep = jnp.asarray(np.arange(F_oh) % 3 != 1)       # drop a third
    fmask = jnp.broadcast_to(
        fl.expand_feature_mask(keep, F_oh, Bp)[:, None],
        (F_oh * Bp, 128)).astype(jnp.bfloat16)
    out.append(("level_pass fmask",
                level(gh5, fmask=fmask, nch=fl.NCH_PRECISE)))
    out.append(("level_pass nch=3",
                level(fl.pack_gh(g, h, w, fl.NCH_FAST), nch=fl.NCH_FAST)))
    out.append(("route_pass packed", lambda interpret: (fl.route_pass(
        bins_pk, c["leaf_T"], W_pk, c["tbl"], interpret=interpret,
        packed=pk, **kw),)))
    return out


def main() -> int:
    import jax

    from chip_smoke import kernel_case
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"probe_kernel_variants: needs a TPU; JAX found "
                 f"platform '{dev.platform}'")
    failed = 0
    for Sp in (8, 128):
        for name, run in _variants(kernel_case(Sp)):
            rec = {"variant": name, "Sp": Sp, "device": dev.device_kind}
            try:
                got = [np.asarray(a) for a in run(False)]
            except Exception as e:   # the probe's product IS the message
                rec.update(ok=False, stage="compile/run", error=str(e)[:1500])
            else:
                want = [np.asarray(a) for a in run(True)]
                close = all(
                    np.array_equal(a, b) if a.dtype.kind in "iu"
                    else np.allclose(a, b, rtol=1e-4, atol=1e-4)
                    for a, b in zip(got, want))
                rec.update(ok=bool(close), stage="compare",
                           max_abs_diff=float(max(
                               np.max(np.abs(a.astype(np.float64) - b))
                               for a, b in zip(got, want))))
            failed += not rec["ok"]
            print(json.dumps(rec), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
