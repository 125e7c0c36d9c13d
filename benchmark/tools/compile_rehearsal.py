#!/usr/bin/env python3
"""Compile-only rehearsal of the level, route and lookup kernels at shapes
the benchmark has no cell for yet, for a described ``v5e:2x2`` chip that
is not attached. No chip time, no result, no timing: it says which shapes
the TPU's compiler accepts today and what it says of those it refuses, so
that the next configuration issue knows which one is one PR away.

  JAX_PLATFORMS=cpu python3 benchmark/tools/compile_rehearsal.py

Shapes are in ``SHAPES`` below (name, features, max_bin); each is tried
at the shallow slot count (8) and at the deepest the program would use
for that width (``max_slot_cap``, at most 128). One JSON line per kernel
and shape.
"""
from __future__ import annotations

import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

SHAPES = [                      # name, features, max_bin
    ("higgs63", 28, 63),        # the benchmark's own width, as a control
    ("higgs255", 28, 255),
    ("msltr63", 137, 63),
    ("msltr255", 137, 255),
    ("epsilon63", 2000, 63),
]
ROWS = 65_536                   # the grid's length only; tiles are per shape
LEAVES = 255


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lightgbm_tpu.ops.fused_level import (NCH_PRECISE, level_pass,
                                              max_slot_cap, route_pass,
                                              table_lookup)
    from lightgbm_tpu.ops.layout import feature_layout

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    def attempt(kernel, name, sp, fn, *args):
        rec = {"kernel": kernel, "shape": name, "slots": sp}
        try:
            compiled = jax.jit(fn).lower(*args).compile()
            mem = compiled.memory_analysis()
            rec.update(compiles=True, temp_bytes=int(
                getattr(mem, "temp_size_in_bytes", 0) or 0))
        except Exception as exc:            # the compiler's own words
            msg = str(exc).strip().splitlines()
            rec.update(compiles=False, error=type(exc).__name__,
                       says=" | ".join(msg[:3])[:600])
        print(json.dumps(rec), flush=True)

    for name, features, max_bin in SHAPES:
        f_oh, bp = feature_layout(features, max_bin)
        fp, fb = max(f_oh, 8), f_oh * bp
        bins = shape((fp, ROWS), jnp.int8 if bp <= 128 else jnp.int16)
        leaf = shape((1, ROWS), jnp.int32)
        gh = shape((8, ROWS), jnp.bfloat16)
        for sp in sorted({8, min(128, max_slot_cap(fb, NCH_PRECISE))}):
            W = shape((sp, fb), jnp.bfloat16)
            tbl = shape((sp, 128), jnp.int32)
            kw = dict(num_slots=sp, num_bins=bp, f_oh=f_oh)
            attempt("level_pass", name, sp, functools.partial(
                level_pass, nch=NCH_PRECISE, **kw), bins, leaf, gh, W, tbl)
            attempt("route_pass", name, sp, functools.partial(
                route_pass, **kw), bins, leaf, W, tbl)
    attempt("table_lookup", "any", LEAVES, table_lookup,
            shape((1, ROWS), jnp.int32), shape((LEAVES,), jnp.float32))


if __name__ == "__main__":
    main()
