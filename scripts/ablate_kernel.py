"""Per-level-pass kernel cost vs slot count on the attached chip.

Separates the one-hot build floor (Sp-independent) from the dot cost
(scales with Sp) by timing level_pass at Sp = 8..128, plus table_lookup.
Run: ROWS=10500000 python scripts/ablate_kernel.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from lightgbm_tpu.ops import fused_level as fl


def main():
    R = int(os.environ.get("ROWS", 10_500_000))
    reps = int(os.environ.get("REPS", 5))
    F, B = fl.feature_layout(28, 63)
    Rp = ((R + 2047) // 2048) * 2048   # widest tile (shallow passes)
    Fp = max(F, 8)
    rng = np.random.RandomState(0)
    bins_T = jnp.asarray(
        rng.randint(0, 63, size=(Fp, Rp)).astype(np.int8))
    leaf_T = jnp.zeros((1, Rp), jnp.int32)
    g = jnp.asarray(rng.randn(Rp).astype(np.float32))
    ones = jnp.ones((Rp,), jnp.float32)

    print(f"rows={R} (padded {Rp}) F_oh={F} B={B}")
    # tiles=0: the Sp-aware default (2048 at shallow Sp since round 4);
    # explicit 1024 reproduces the round-2/3 fixed tile for the A/B
    tile_list = [int(t) for t in
                 os.environ.get("TILES", "0,1024").split(",")]
    for nch in (5, 3):
        gh_T = fl.pack_gh(g, ones, ones, nch)
        for Sp in (1, 2, 4, 8, 16, 32, 64, 128):
            W = jnp.zeros((Sp, F * B), jnp.bfloat16).at[0, :B].set(1)
            tbl = (jnp.zeros((Sp, 128), jnp.int32)
                   .at[:, 0].set(-2).at[0, 0].set(0).at[0, 2].set(1))

            for tile in tile_list:
                # the passes chain through the leaf vector;
                # block_until_ready is the clock
                def one(lt):
                    h, nl = fl.level_pass(bins_T, lt, gh_T, W, tbl,
                                          num_slots=Sp, num_bins=B,
                                          f_oh=F, nch=nch, tile_rows=tile)
                    return h, nl
                jax.block_until_ready(one(leaf_T))
                t0 = time.perf_counter()
                lt = leaf_T
                for _ in range(reps):
                    h, lt = one(lt)
                jax.block_until_ready((h, lt))
                dt = (time.perf_counter() - t0) / reps
                bw = Fp * Rp / dt / 1e9
                eff_tile = tile or fl.default_tile_rows(Sp, F * B, nch)
                print(f"  nch={nch} Sp={Sp:4d} tile={eff_tile:5d}"
                      f"  {dt*1e3:8.1f} ms/pass  ({bw:5.1f} GB/s bins)")

    table = jnp.asarray(rng.randn(255).astype(np.float32))
    idx = jnp.asarray(rng.randint(0, 255, size=(1, Rp)).astype(np.int32))
    jax.block_until_ready(fl.table_lookup(idx, table))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fl.table_lookup(idx, table)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / reps
    print(f"  table_lookup 255-entry      {dt*1e3:8.1f} ms/pass")


if __name__ == "__main__":
    main()
