"""Spatial tiling: the halo'd H/W-streaming conv kernel vs the oracle, the
joint TilePlan planner's invariants, the working-set accounting fix, the
spatial-sharded scheduler mode, and the large-map acceptance path (a conv
layer whose whole-map working set exceeds the VMEM budget streaming
bit-exactly through halo'd tiles)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import banking, network, perfmodel, scheduler
from repro.core.banking import TilePlan, plan_banks, plan_tiles
from repro.core.convcore import ConvCore, ConvCoreConfig, get_backend
from repro.kernels import ref
from repro.kernels.conv2d_ws import conv2d_ws

RNG = np.random.default_rng(23)


def _i8(*shape):
    return jnp.asarray(RNG.integers(-128, 128, size=shape), jnp.int8)


def _f32(*shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


# ---------------------------------------------------------------------------
# Tiled kernel vs oracle (deterministic grid of the hard cases)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride", [1, 2, 3])
@pytest.mark.parametrize("padding", ["VALID", "SAME"])
def test_tiled_int8_bit_exact_strides(stride, padding):
    """Tile sizes that do NOT divide the output, every stride, both
    canonical paddings — int8 is bit-exact, no tolerance."""
    x, w = _i8(2, 17, 13, 8), _i8(3, 3, 8, 8)
    b = jnp.asarray(RNG.integers(-500, 500, (8,)), jnp.int32)
    got = conv2d_ws(x, w, b, stride=stride, padding=padding,
                    h_tile=3, w_tile=5, interpret=True)
    want = ref.conv2d_ref_int8(x, w, b, stride=stride, padding=padding)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("kh,kw", [(1, 3), (5, 2), (2, 4)])
def test_tiled_nonsquare_kernels(kh, kw):
    x, w = _i8(1, 14, 15, 4), _i8(kh, kw, 4, 4)
    got = conv2d_ws(x, w, h_tile=4, w_tile=6, interpret=True)
    want = ref.conv2d_ref_int8(x, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tiled_explicit_padding():
    x, w = _i8(1, 11, 9, 4), _i8(3, 3, 4, 8)
    pad = ((2, 1), (0, 2))
    got = conv2d_ws(x, w, padding=pad, h_tile=5, w_tile=4, interpret=True)
    want = ref.conv2d_ref_int8(x, w, padding=pad)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tiled_fused_epilogue_pool_aligned():
    """ReLU → 2×2 pool → requantize, tile-local: even tiles keep pool
    windows inside tiles and the result bit-matches the oracle chain."""
    x, w = _i8(2, 18, 14, 8), _i8(3, 3, 8, 8)
    b = jnp.asarray(RNG.integers(-500, 500, (8,)), jnp.int32)
    sc = jnp.asarray(RNG.uniform(5e-4, 2e-3, (8,)), jnp.float32)
    got = conv2d_ws(x, w, b, sc, padding="SAME", h_tile=4, w_tile=6,
                    relu=True, pool=True, interpret=True)
    want = ref.conv2d_epilogue_ref(x, w, b, padding="SAME", relu=True,
                                   pool=True, out_scale=sc)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pool_rejects_unaligned_tiles():
    x, w = _i8(1, 12, 12, 4), _i8(3, 3, 4, 4)
    with pytest.raises(AssertionError):
        conv2d_ws(x, w, padding="SAME", h_tile=3, w_tile=4, pool=True,
                  interpret=True)


def test_tiled_float_matches_oracle():
    x, w, b = _f32(1, 13, 17, 4), _f32(3, 3, 4, 8), _f32(8)
    got = conv2d_ws(x, w, b, stride=2, padding="SAME", h_tile=2, w_tile=4,
                    interpret=True)
    want = ref.conv2d_ref(x, w, b, stride=2, padding="SAME")
    np.testing.assert_allclose(got, want, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# Hypothesis sweep (guarded import, like tests/test_property.py)
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def tiled_case(draw):
        h = draw(st.integers(6, 16))
        w = draw(st.integers(6, 16))
        kh = draw(st.integers(1, 4))
        kw = draw(st.integers(1, 4))
        stride = draw(st.sampled_from([1, 2, 3]))
        padding = draw(st.sampled_from(
            ["VALID", "SAME", ((draw(st.integers(0, 2)),
                                draw(st.integers(0, 2))),
                               (draw(st.integers(0, 2)),
                                draw(st.integers(0, 2))))]))
        oh, ow = ref.conv_out_shape(h, w, kh, kw, stride, padding)
        if oh < 1 or ow < 1:
            h, w, padding = h + kh, w + kw, "SAME"
            oh, ow = ref.conv_out_shape(h, w, kh, kw, stride, padding)
        th = draw(st.integers(1, max(1, oh)))
        tw = draw(st.integers(1, max(1, ow)))
        seed = draw(st.integers(0, 2**31 - 1))
        return h, w, kh, kw, stride, padding, th, tw, seed

    @given(tiled_case())
    @settings(max_examples=25, deadline=None)
    def test_tiled_conv_bit_exact_property(case):
        """Tiled == untiled == oracle, bit-exact, for arbitrary strides,
        paddings, non-square kernels, and non-dividing tile sizes."""
        h, w, kh, kw, stride, padding, th, tw, seed = case
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(-128, 128, (1, h, w, 4)), jnp.int8)
        wt = jnp.asarray(rng.integers(-128, 128, (kh, kw, 4, 4)), jnp.int8)
        got = conv2d_ws(x, wt, stride=stride, padding=padding,
                        h_tile=th, w_tile=tw, interpret=True)
        want = ref.conv2d_ref_int8(x, wt, stride=stride, padding=padding)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @given(st.integers(8, 320), st.integers(8, 320),
           st.sampled_from([4, 8, 16, 64]), st.sampled_from([4, 16, 64]),
           st.sampled_from([1, 2]), st.booleans())
    @settings(max_examples=30, deadline=None)
    def test_plan_tiles_invariants(h, w, c, k, stride, pool):
        """plan_tiles: working set fits the budget (or nothing can shrink
        further), tiles are pool-aligned row bands of the full width,
        banks divide the channels into lane-legal blocks, and tiles cover
        the output."""
        budget = 1 << 20                       # 1 MiB: forces real tiling
        oh, ow = ref.conv_out_shape(h, w, 3, 3, stride, "SAME")
        if pool and (oh < 2 or ow < 2):
            pool = False
        p = plan_tiles(h, w, c, k, stride=stride, padding="SAME",
                       pool=pool, in_bytes=1, out_bytes=1,
                       vmem_budget=budget)
        assert c % p.cin_banks == 0 and k % p.kout_banks == 0
        assert ref.lane_legal_banks(c, p.cin_banks)
        assert ref.lane_legal_banks(k, p.kout_banks)
        assert p.w_tile == p.out_w
        if pool:
            assert p.h_tile % 2 == 0 and p.w_tile % 2 == 0
        assert p.n_h_tiles * p.h_tile >= p.out_h
        assert p.n_w_tiles * p.w_tile >= p.out_w
        # recompute the working set from first principles
        cb, kb = c // p.cin_banks, k // p.kout_banks
        assert p.image_block_bytes == p.in_h_tile * p.in_w_tile * cb
        assert p.acc_block_bytes == p.h_tile * p.w_tile * kb * 4
        if not p.fits_vmem:
            # only legal when maximally split: minimal row bands AND no
            # lane-legal bank doubling left
            min_tile = 2 if pool else 1
            assert p.h_tile <= min_tile
            assert not ref.lane_legal_banks(c, 2 * p.cin_banks)
            assert not ref.lane_legal_banks(k, 2 * p.kout_banks)


# ---------------------------------------------------------------------------
# Working-set accounting (the BankPlan undercount fix)
# ---------------------------------------------------------------------------


def test_bankplan_counts_acc_and_output_separately():
    plan = plan_banks(64, 64, 8, 8, in_bytes=1, out_bytes=1)
    # epilogue output (int8) and accumulator scratch (int32) are distinct
    oh = ow = 62
    assert plan.output_block_bytes == oh * ow * 2 * 1
    assert plan.acc_block_bytes == oh * ow * 2 * 4
    assert plan.working_set_bytes == (
        2 * (plan.image_block_bytes + plan.weight_block_bytes
             + plan.output_block_bytes) + plan.acc_block_bytes)


def test_tileplan_working_set_separates_acc():
    """The working set counts blocks as Mosaic lays them out (lanes to
    128, sublanes to the dtype tile): double-buffered input, weight,
    int8 output, bias and scale blocks, the int32 accumulator once, and
    the kernel body's values (input window, one tap slice, four
    accumulator-sized values)."""
    p = plan_tiles(64, 64, 8, 8, in_bytes=1, out_bytes=1, pool=False,
                   vmem_budget=None)
    lay = banking.laid_out_bytes
    assert p.working_set_bytes == (                  # VALID: 64 → 62
        2 * (lay((64, 64, 8), 1) + lay((3, 3, 8, 8), 1)
             + lay((62, 62, 8), 1) + 2 * lay((1, 8), 4))
        + lay((62, 62, 8), 4)
        + lay((64, 64, 8), 1) + lay((62 * 62, 8), 1)
        + 4 * lay((62 * 62, 8), 4))
    assert lay((62, 62, 8), 1) == 62 * 64 * 128      # 32-row int8 tiles
    assert lay((62, 62, 8), 4) == 62 * 64 * 128 * 4  # 8-row int32 tiles
    assert p.acc_block_bytes == p.h_tile * p.w_tile * (8 // p.kout_banks) * 4


def test_pooled_tiny_output_planner_and_kernel_agree():
    """Regression: plan_tiles(pool=True) used to clamp a 1×1 conv output
    to a phantom 2×2 pooled map — reporting nonzero tile traffic for a
    layer conv2d_ws rejects.  Planner and kernel now raise the same
    error."""
    with pytest.raises(ValueError, match="2×2 pool"):
        plan_tiles(3, 3, 4, 4, padding="VALID", pool=True, in_bytes=1)
    x, w = _i8(1, 3, 3, 4), _i8(3, 3, 4, 4)       # VALID → 1×1 conv output
    with pytest.raises(ValueError, match="2×2 pool"):
        conv2d_ws(x, w, pool=True, interpret=True)
    # 2×2 output is the smallest legal pooled map: both accept it
    p = plan_tiles(4, 4, 4, 4, padding="VALID", pool=True, in_bytes=1)
    assert (p.out_h, p.out_w) == (2, 2)


def test_resnet_tile_plans_compile():
    """Residual-graph plans route per-node input shapes into the planner:
    every conv (including 1×1 projection shortcuts) gets a fitting plan."""
    for plan in (network.resnet_small(), network.resnet_bottleneck()):
        tps = plan.tile_plans()
        convs = [tp for tp in tps if tp is not None]
        assert len(convs) == sum(
            1 for sp in plan.layers if sp.kind == "conv")
        assert all(tp.fits_vmem for tp in convs), plan.name


# ---------------------------------------------------------------------------
# ConvCore planning + spatial-sharded scheduler
# ---------------------------------------------------------------------------


def test_convcore_plans_tiles_for_large_maps():
    core = ConvCore(ConvCoreConfig(int8=True))
    plan = core.plan((1, 512, 512, 64), (3, 3, 64, 64), 1, "SAME")
    assert plan.tiled and plan.fits_vmem
    # small maps keep the whole-map single tile; the paper's 4×4 banking
    # degrades to lane-legal banks (full-extent or 128-channel blocks)
    small = core.plan((1, 28, 28, 8), (3, 3, 8, 8), 1, "SAME")
    assert not small.tiled
    assert small.cin_banks == 1 and small.kout_banks == 1
    wide = core.plan((1, 14, 14, 256), (3, 3, 256, 512), 1, "SAME")
    assert not wide.tiled
    assert wide.cin_banks == 2 and wide.kout_banks == 4


@pytest.mark.parametrize("pool", [False, True])
def test_spatial_sharded_backend_exact(pool):
    """Halo'd row bands across virtual cores == the unsharded conv,
    bit-exact, including the fused pool epilogue (pool-aligned bands)."""
    inner = get_backend("ref")
    sb = scheduler.SpatialShardedBackend(inner, 3)
    x, w = _i8(2, 19, 11, 4), _i8(3, 3, 4, 8)
    b = jnp.asarray(RNG.integers(-300, 300, (8,)), jnp.int32)
    got = sb.conv(x, w, b, stride=1, padding="SAME", relu=True, pool=pool)
    want = inner.conv(x, w, b, stride=1, padding="SAME", relu=True,
                      pool=pool)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_spatial_mode_network_bit_identical():
    plan = network.lenet()
    params = plan.init_params(RNG)
    x = jnp.asarray(RNG.normal(size=(2, *plan.input_shape)), jnp.float32)
    qnet = network.quantize_network(plan, params, x)
    base = network.make_int8_program(
        qnet, ConvCoreConfig(backend="ref", int8=True))(x)
    sched = scheduler.MultiCoreScheduler(
        scheduler.SchedulerConfig(n_cores=4, mode="spatial"))
    sb = sched.shard_backend("ref")
    from repro.core.convcore import register_backend
    register_backend(sb)
    got = sched.run(network.make_int8_program(
        qnet, ConvCoreConfig(backend=sb.name, int8=True)), x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


# ---------------------------------------------------------------------------
# Perfmodel: tile revisits + halo re-reads
# ---------------------------------------------------------------------------


def test_tile_traffic_prices_halo_rereads():
    p = plan_tiles(512, 512, 64, 64, stride=1, padding="SAME",
                   in_bytes=1, out_bytes=1)
    assert p.tiled
    t = perfmodel.tile_traffic(p)
    assert t["halo_read_factor"] > 1.0          # halos are re-read
    assert t["kout_revisits"] == p.kout_banks   # input re-read per kernel set
    assert t["total_bytes"] == (t["input_bytes"] + t["weight_bytes"]
                                + t["output_bytes"])


def test_network_report_tile_pricing_keeps_defaults():
    """Without tile plans the §5.2 numbers are untouched; with plans,
    layer cycles floor at the DMA time and the shared-DDR bound keeps the
    20-core estimate honest."""
    plan = network.large_map()
    base = plan.perf_report()
    priced = plan.perf_report(tile_plans=plan.tile_plans())
    assert priced["cycles"] >= base["cycles"]
    l0 = priced["layers"][0]
    assert l0["n_tiles"] > 1 and l0["halo_read_factor"] > 1.0
    assert l0["cycles"] >= l0["dma_cycles"]
    # the DMA floor does not shrink with 20 cores (shared interface)
    assert priced["full_board"]["cycles"] >= sum(
        r["dma_cycles"] for r in priced["layers"] if "dma_cycles" in r)
    # default-path regression: lenet keeps the paper's numbers exactly
    rep = network.lenet().perf_report()
    assert rep["gops_paper"] == pytest.approx(0.224, rel=1e-2)


# ---------------------------------------------------------------------------
# Acceptance: a conv layer larger than the VMEM budget streams through
# halo'd spatial tiles, bit-exact vs the oracle
# ---------------------------------------------------------------------------


def test_large_map_layer_exceeds_budget_and_runs_tiled():
    """512×512×64 → 64, batch 4, SAME: the whole-map working set exceeds
    the VMEM budget; the planned tiled kernel is bit-exact vs ref."""
    whole = plan_tiles(512, 512, 64, 64, stride=1, padding="SAME",
                       in_bytes=1, out_bytes=4, vmem_budget=None)
    # the seed's whole-map dataflow could not fit
    assert whole.working_set_bytes > banking.VMEM_LIMIT_BYTES
    p = plan_tiles(512, 512, 64, 64, stride=1, padding="SAME",
                   in_bytes=1, out_bytes=4)
    assert p.tiled and p.fits_vmem
    x, w = _i8(4, 512, 512, 64), _i8(3, 3, 64, 64)
    got = conv2d_ws(x, w, stride=1, padding="SAME",
                    cin_banks=p.cin_banks, kout_banks=p.kout_banks,
                    h_tile=p.h_tile, w_tile=p.w_tile, interpret=True)
    want = ref.conv2d_ref_int8(x, w, stride=1, padding="SAME")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
