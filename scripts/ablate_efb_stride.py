"""EFB uniform-stride padding waste measurement (VERDICT r4 item 8).

The reference stores bundles with jagged per-group offsets
(ref: src/io/dataset.cpp:108-176 — each FeatureGroup's bin range is
exactly the sum of its members' bins); the fused kernel's one-hot bin
extraction needs a UNIFORM per-column stride, so bundle columns are
padded to the widest and the adaptive cap (gbdt.py _setup_bundles)
tightens the bundle width only when padding would inflate storage >2x.

This script measures, for realistic feature-width mixes, what the
uniform padding actually costs relative to (a) the jagged ideal and
(b) the reference's uncapped bundling, plus how much bundling the cap
abandons. A per-column stride table (scalar-prefetched offsets into the
one-hot scratch) would recover the jagged layout on-chip — whether the
extra scalar loads beat the padded dot is the HARDWARE half of this
ablation (scripts/ablate_kernel.py territory, not measured yet);
this half records the storage side either way.

Run: PYTHONPATH=/root/repo python scripts/ablate_efb_stride.py
"""
import numpy as np

from lightgbm_tpu.ops.efb import BundleLayout, find_bundles

RNG = np.random.RandomState(0)


def synth(kind, n=20000, F=200):
    """Sparse one-hot-ish feature sets with a given bin-width mix."""
    if kind == "uniform-small":        # OHE-style: all features 3 bins
        widths = np.full(F, 3)
    elif kind == "mixed":              # realistic: mostly small, a few wide
        widths = np.where(RNG.rand(F) < 0.9,
                          RNG.randint(2, 8, F), RNG.randint(64, 256, F))
    elif kind == "adversarial":        # the width mix the cap fears:
        widths = np.where(np.arange(F) % 10 == 0, 255, 2)
    else:
        raise ValueError(kind)
    # group features into near-exclusive cliques of ~10
    owner = RNG.randint(0, F // 10, n)
    masks = []
    for f in range(F):
        m = np.zeros(n, bool)
        m[owner == f // 10] = RNG.rand((owner == f // 10).sum()) < 0.9
        masks.append(m)
    return masks, [int(w) for w in widths]


def measure(kind):
    masks, widths = synth(kind)
    n = len(masks[0])
    F = len(masks)
    rows = []
    for cap_name, cap in (("uncapped(int16)", 32767),
                          ("8x max_bin(2040)", 2040),
                          ("4x max_bin(1020)", 1020)):
        bundles = find_bundles(masks, n, max_conflict_rate=1e-4,
                               max_bundle_bins=cap,
                               num_bin_per_feat=widths)
        col_widths = [1 + sum(widths[f] for f in b) for b in bundles]
        jagged = sum(col_widths)              # reference storage units
        padded = len(bundles) * max(col_widths) if bundles else 0
        rows.append((cap_name, len(bundles), jagged, padded,
                     padded / max(1, jagged)))
    print(f"\n== {kind}: F={F}, widths min/med/max = "
          f"{min(widths)}/{int(np.median(widths))}/{max(widths)}")
    print(f"{'cap':>18} {'cols':>6} {'jagged':>8} {'padded':>8} "
          f"{'pad/jag':>8}")
    for r in rows:
        print(f"{r[0]:>18} {r[1]:>6} {r[2]:>8} {r[3]:>8} {r[4]:>8.2f}")
    return rows


if __name__ == "__main__":
    for kind in ("uniform-small", "mixed", "adversarial"):
        measure(kind)
    print("\n(adaptive cap keeps the first row whose pad/jag <= 2.0 — "
          "gbdt.py _setup_bundles)")
