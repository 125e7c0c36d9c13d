"""The level and route kernels COMPILED for a described TPU v5e, no chip
attached (the on-chip-measurement guide's third rehearsal), at the
benchmark's widths and in both routing forms. Interpret mode cannot see
what the TPU's compiler refuses: a slice off the tiling, a matmul shape,
more scoped VMEM than a kernel may use. A compile that passes is not a
chip run: nothing here says anything about results or times.

The topology is described inside a fixture (one process at a time may
load the TPU's library; every xdist worker imports this file), and all
such tests live in this one file.
"""
import functools
import os

import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.ops.fused_level import (NCH_PRECISE, default_tile_rows,
                                          feature_layout, level_pass,
                                          max_slot_cap, route_pass,
                                          route_tile_rows)

ROWS = 65_536       # the grid's length only; tiles are per shape


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


def _operands(chip, features, max_bin, sp, has_cat=False):
    f_oh, bp = feature_layout(features, max_bin)
    fp = max(f_oh, 8)
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=chip)
    return dict(
        bins=shape((fp, ROWS), jnp.int8 if bp <= 128 else jnp.int16),
        leaf=shape((1, ROWS), jnp.int32), gh=shape((8, ROWS), jnp.bfloat16),
        W=shape((sp, f_oh * bp), jnp.bfloat16),
        tbl=shape((sp, 128), jnp.int32),
        kw=dict(num_slots=sp, num_bins=bp, f_oh=f_oh, has_cat=has_cat),
        fp=fp, fb=f_oh * bp)


def _compiles(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# name, features, max_bin, has_cat: the benchmark's widths, and Higgs at
# 255 bins. expo255-cat is the categorical cell as it runs since PR 34:
# int16 bins, FB 2,048, the bins form with the set-membership test traced
# (``has_cat``; the table form ignores it); expo255 the same width without
WIDTHS = [("higgs63", 28, 63, False), ("msltr63", 137, 63, False),
          ("higgs255", 28, 255, False), ("expo255", 8, 255, False),
          ("expo255-cat", 8, 255, True)]


@pytest.mark.parametrize("form", ["bins", "table"])
@pytest.mark.parametrize("deep", [False, True], ids=["8slots", "cap"])
@pytest.mark.parametrize("name,features,max_bin,has_cat", WIDTHS,
                         ids=[w[0] for w in WIDTHS])
def test_level_and_route_kernels_compile(one_chip, name, features, max_bin,
                                         has_cat, deep, form):
    fb = feature_layout(features, max_bin)
    sp = min(128, max_slot_cap(fb[0] * fb[1], NCH_PRECISE)) if deep else 8
    o = _operands(one_chip, features, max_bin, sp, has_cat)
    W = o["W"] if form == "table" else None
    _compiles(functools.partial(level_pass, nch=NCH_PRECISE, **o["kw"]),
              o["bins"], o["leaf"], o["gh"], W, o["tbl"])
    _compiles(functools.partial(route_pass, **o["kw"]),
              o["bins"], o["leaf"], W, o["tbl"])


def _pallas_call(fn, *args):
    """The pallas_call equation of a traced kernel wrapper."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = find(sub)
                if found is not None:
                    return found
    return find(jax.make_jaxpr(fn)(*args).jaxpr)


# (name, deep) -> the row tile of the bins form (slab build, PR 31) and of
# the table form (whole [FB, C] scratch: the tiles of PR 21-29)
TILES = {("higgs63", False): (2048, 1024), ("higgs63", True): (2048, 1024),
         ("msltr63", False): (2048, 256), ("msltr63", True): (2048, 128),
         ("higgs255", False): (2048, 256), ("higgs255", True): (2048, 256),
         ("expo255", False): (2048, 1024), ("expo255", True): (2048, 1024),
         ("expo255-cat", False): (2048, 1024),
         ("expo255-cat", True): (2048, 1024)}


@pytest.mark.parametrize("deep", [False, True], ids=["8slots", "cap"])
@pytest.mark.parametrize("name,features,max_bin,has_cat", WIDTHS,
                         ids=[w[0] for w in WIDTHS])
def test_bins_form_level_kernel_has_no_fb_sized_scratch(one_chip, name,
                                                        features, max_bin,
                                                        has_cat, deep):
    """The bins form builds its one-hot in slabs: no scratch operand at
    all, and the row tile no longer shrinks with FB (the compile test
    above holds that these tiles fit the default 16 MB of scoped VMEM).
    The table form keeps its [FB, C] scratch at the tile it had. The
    membership planes of a categorical job are charged to the tile
    (CAT_PLANE_BYTES) and leave it 2,048 rows at the cell's 64 slots."""
    f_oh, bp = feature_layout(features, max_bin)
    fb = f_oh * bp
    sp = min(128, max_slot_cap(fb, NCH_PRECISE)) if deep else 8
    o = _operands(one_chip, features, max_bin, sp, has_cat)
    want_bins, want_table = TILES[name, deep]
    assert default_tile_rows(sp, fb, NCH_PRECISE, bins_rows=o["fp"],
                             has_cat=has_cat) == want_bins
    assert default_tile_rows(sp, fb, NCH_PRECISE) == want_table
    fn = functools.partial(level_pass, nch=NCH_PRECISE, **o["kw"])
    args = (o["bins"], o["leaf"], o["gh"])
    bins_call = _pallas_call(fn, *args, None, o["tbl"])
    assert bins_call.params["grid_mapping"].num_scratch_operands == 0
    assert bins_call.params["grid_mapping"].block_mappings[0] \
        .block_shape[1].block_size == want_bins
    table_call = _pallas_call(fn, *args, o["W"], o["tbl"])
    assert table_call.params["grid_mapping"].num_scratch_operands == 1
    assert table_call.params["jaxpr"].invars[-1].aval.shape \
        == (fb, want_table)


def test_bins_form_kernels_at_epsilon_width(one_chip):
    """Epsilon's width (2,000 features, FB 128,000). ``route_pass``: the
    table form needs 31 MB of scoped VMEM for its one-hot and is refused;
    the bins form holds the [Fp, C] bin tile alone and compiles (ROADMAP
    B-I.3). ``level_pass`` in the table form is refused too. Its bins
    form has no scratch left to refuse (test below, slow: 250 unrolled
    slabs)."""
    o = _operands(one_chip, 2000, 63, 8)
    assert route_tile_rows(8, o["fp"]) == 1024
    assert default_tile_rows(8, o["fb"], NCH_PRECISE,
                             bins_rows=o["fp"]) == 1024
    _compiles(functools.partial(route_pass, **o["kw"]),
              o["bins"], o["leaf"], None, o["tbl"])
    with pytest.raises(Exception, match="vmem"):
        _compiles(functools.partial(route_pass, **o["kw"]),
                  o["bins"], o["leaf"], o["W"], o["tbl"])
    with pytest.raises(Exception, match="vmem"):
        _compiles(functools.partial(level_pass, nch=NCH_PRECISE, **o["kw"]),
                  o["bins"], o["leaf"], o["gh"], o["W"], o["tbl"])


@pytest.mark.slow
def test_bins_form_level_kernel_compiles_at_epsilon_width(one_chip):
    """What the compiler says once the scratch is gone: the bins-form
    ``level_pass`` at FB 128,000 compiles at 1,024-row tiles (107 s for
    250 unrolled slabs; the [128,000, 40] float32 accumulator is the
    pipeline's output window, not scoped stack). Compiled, never run."""
    o = _operands(one_chip, 2000, 63, 8)
    _compiles(functools.partial(level_pass, nch=NCH_PRECISE, **o["kw"]),
              o["bins"], o["leaf"], o["gh"], None, o["tbl"])


# ---- GOSS on the fast path (PR 35): the sampled step's own shapes
GOSS_ROWS, GOSS_CAPACITY = 28_000_256, 8_400_896   # the cell's Rp and K


@pytest.mark.parametrize("tile", [4096, 8192, 16384])
def test_the_row_compaction_compiles_at_the_cell_s_rows(one_chip, tile):
    """``ops/goss.compact_rows`` at 28M rows -> 8.4M columns, Higgs width,
    in VMEM: the [40, 2C + 128] float32 window (5.3 MB at the cell's
    16,384-row tile), the double-buffered bin, channel and int32
    destination tiles and the two output blocks; per 128-row chunk a
    [128, 128] bfloat16 rotation and the chunk's [40, 128] columns (C / 128
    chunks unrolled); in SMEM the scalar-prefetched block table (one int32
    a tile) and each tile's C / 128 chunk offsets. 8,400,896 is no
    multiple of 16,384: the last output block is partial."""
    from lightgbm_tpu.ops import goss
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)
    _compiles(functools.partial(goss.compact_rows, capacity=GOSS_CAPACITY,
                                tile_rows=tile),
              shape((32, GOSS_ROWS), jnp.int8),
              shape((8, GOSS_ROWS), jnp.bfloat16),
              shape((28_000_000,), jnp.bool_))


def test_the_compaction_compiles_for_int16_bins(one_chip):
    from lightgbm_tpu.ops import goss
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)
    _compiles(functools.partial(goss.compact_rows, capacity=GOSS_CAPACITY),
              shape((8, GOSS_ROWS), jnp.int16),
              shape((8, GOSS_ROWS), jnp.bfloat16),
              shape((28_000_000,), jnp.bool_))


def test_the_sampler_compiles_at_the_cell_s_rows(one_chip):
    from lightgbm_tpu.ops import goss
    _compiles(lambda a: goss.goss_sample(a, 12, 3, 5_600_000, 2_800_000),
              jax.ShapeDtypeStruct((28_000_000,), jnp.float32,
                                   sharding=one_chip))


@pytest.mark.parametrize("deep", [False, True], ids=["8slots", "cap"])
def test_level_pass_compiles_at_the_sample_s_capacity(one_chip, deep):
    """``level_pass`` over K = 8,400,896 columns (4,102 tiles of 2,048), the
    row count a sampled tree is grown on, beside the 28M-row one."""
    fb = feature_layout(28, 63)
    sp = min(128, max_slot_cap(fb[0] * fb[1], NCH_PRECISE)) if deep else 8
    shape = lambda dims, dt: jax.ShapeDtypeStruct(dims, dt, sharding=one_chip)
    assert GOSS_CAPACITY % 2048 == 0
    _compiles(functools.partial(level_pass, nch=NCH_PRECISE, num_slots=sp,
                                num_bins=fb[1], f_oh=fb[0]),
              shape((32, GOSS_CAPACITY), jnp.int8),
              shape((1, GOSS_CAPACITY), jnp.int32),
              shape((8, GOSS_CAPACITY), jnp.bfloat16), None,
              shape((sp, 128), jnp.int32))


# ---- the histogram dot with the channels streamed (PR 36): the bf16 slab
# build latches the one-hot's tiles and accumulates into a transposed
# [nch*Sp, FB] window
# name, features, max_bin, has_cat, slots, rows: Higgs's and the
# categorical cell's deep passes, the GOSS cell's compact width and the
# ranking cell's passes at its slot cap
CHANNEL_PASSES = [("higgs63", 28, 63, False, 32, ROWS),
                  ("higgs63", 28, 63, False, 64, ROWS),
                  ("expo255-cat", 8, 255, True, 64, ROWS),
                  ("higgs63-goss", 28, 63, False, 64, GOSS_CAPACITY),
                  ("msltr63", 137, 63, False, 16, ROWS)]


@pytest.mark.parametrize("name,features,max_bin,has_cat,sp,rows",
                         CHANNEL_PASSES,
                         ids=["%s-%d" % (c[0], c[4]) for c in CHANNEL_PASSES])
def test_the_channel_streaming_pass_compiles_within_its_charge(
        one_chip, name, features, max_bin, has_cat, sp, rows):
    """What ``level_build`` wires in the bf16 slab build compiles for the
    described v5e at 2,048-row tiles, its accumulator is the transposed
    window, and the scoped VMEM the compiler takes is under what
    ``default_tile_rows`` charges the tile (the same operands, the same
    bytes as the other order: the charge did not change). Compiled: 2.39 MB
    at Higgs's 32 slots (the other order 3.37), 4.31 at 64 (5.05), 5.67 in
    the categorical cell at 64 (5.77), 4.25 at the ranking cell's width and
    16 slots (4.90), against charges of 8.3-11.4 MB."""
    from lightgbm_tpu.ops.fused_level import level_build, slab_row_bytes
    from lightgbm_tpu.utils.platform import scoped_vmem_bytes
    o = _operands(one_chip, features, max_bin, sp, has_cat)
    build = level_build(True, sp, o["fb"], NCH_PRECISE, o["fp"],
                        has_cat=has_cat)
    assert build == {"form": "slab", "slab_rows": 512, "tile_rows": 2048,
                     "dot": "channels"}
    shape = lambda like: jax.ShapeDtypeStruct(
        (like.shape[0], rows), like.dtype, sharding=one_chip)
    fn = functools.partial(level_pass, nch=NCH_PRECISE, **o["kw"])
    args = (shape(o["bins"]), shape(o["leaf"]), shape(o["gh"]), None,
            o["tbl"])
    call = _pallas_call(fn, *args)
    assert call.outvars[0].aval.shape == (NCH_PRECISE * sp, o["fb"])
    assert jax.eval_shape(fn, *args)[0].shape == (o["fb"], NCH_PRECISE * sp)
    charge = 2048 * slab_row_bytes(sp, NCH_PRECISE, o["fp"], has_cat)
    used = scoped_vmem_bytes(_compiles(fn, *args))
    print(f"{name} {sp} slots: compiled {used} B, charged {charge} B")
    assert used < charge < 16 * 1024 * 1024


# ---- the bundled job in the bins form (the one-hot cell): 13 kernel
# columns of EFB bundles (14 where a draw bundles into one more), 256 bins
# each (int16), the window decode traced
@pytest.mark.parametrize("cols", [13, 14])
@pytest.mark.parametrize("sp", [8, 32])
def test_the_bundled_bins_form_compiles_within_its_charge(one_chip, sp,
                                                          cols):
    """``level_pass`` and ``route_pass`` with ``bundled``: the decode's
    three [Sp, C] planes are charged to the tile (DECODE_PLANE_BYTES), the
    level pass keeps its 2,048-row tile at the cell's 32-slot cap, and the
    scoped VMEM the compiler takes is under the charge."""
    from lightgbm_tpu.ops.fused_level import level_build, slab_row_bytes
    from lightgbm_tpu.utils.platform import scoped_vmem_bytes
    o = _operands(one_chip, cols, 255, sp)
    assert o["fb"] == cols * 256 and max_slot_cap(o["fb"], NCH_PRECISE) == 32
    build = level_build(True, sp, o["fb"], NCH_PRECISE, o["fp"],
                        bundled=True)
    assert build["tile_rows"] == 2048 and build["dot"] == "channels"
    fn = functools.partial(level_pass, nch=NCH_PRECISE, bundled=True,
                           **o["kw"])
    used = scoped_vmem_bytes(_compiles(fn, o["bins"], o["leaf"], o["gh"],
                                       None, o["tbl"]))
    charge = 2048 * slab_row_bytes(sp, NCH_PRECISE, o["fp"], bundled=True)
    print(f"bundled {cols} columns, {sp} slots: compiled {used} B, "
          f"charged {charge} B")
    assert used < charge < 16 * 1024 * 1024
    assert route_tile_rows(sp, o["fp"], bundled=True) == 8192
    _compiles(functools.partial(route_pass, bundled=True, **o["kw"]),
              o["bins"], o["leaf"], None, o["tbl"])


def test_the_gradient_split_survives_the_tpu_compiler(one_chip):
    """``pack_gh``'s high half is rounded on the value's bits in the
    program compiled for a described v5e: XLA may keep a bfloat16
    intermediate at float32, which turned ``x - f32(bf16(x))`` into 0 on
    the chip (every low half 0, the histogram bfloat16's); integer
    operations it cannot drop."""
    from lightgbm_tpu.ops.fused_level import pack_gh
    x = jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip)
    text = _compiles(lambda g, h: pack_gh(g, h, jnp.ones_like(g),
                                          NCH_PRECISE), x, x).as_text()
    assert " and(" in text and "bitcast-convert" in text
