"""The explicit double-buffered DMA conv pipeline (kernels/conv2d_ws_pipe)
and its planner/cost-model contract:

* bit-exactness vs conv2d_ws across stride × padding × epilogue × groups ×
  tiling (deterministic hard cases + a hypothesis sweep), on the int8 AND
  float accumulator paths, whole networks under every scheduler mode;
* VMEM accounting: the ping-pong working set IS the working set
  ``plan_tiles`` already budgets (the ×2 double-buffer term), so the
  kernel choice never changes whether a plan fits, and budget
  degradation still yields legal plans, dense and grouped;
* the slab rule (``TilePlan.pipelined`` ⇔ more than one slab) and the
  §5.2 model's pricing of it: anchors untouched, depthwise
  ``dma_bound_board`` layers pipelined, one-slab layers sequential, and
  ``network_report`` charging each row its plan's kernel;
* the one-slab layers of ResNet-50 run bit-identically on either kernel.

On a TPU host these tests compile natively (the CI smoke lane);
elsewhere they run in interpret mode.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import banking, network, perfmodel, scheduler
from repro.core.banking import plan_tiles
from repro.core.convcore import (ConvCoreConfig, get_backend,
                                 register_backend)
from repro.kernels import ops, ref
from repro.kernels.conv2d_ws import conv2d_ws
from repro.kernels.conv2d_ws_pipe import conv2d_ws_pipe

RNG = np.random.default_rng(47)

# native Mosaic on TPU (the CI smoke lane), interpret everywhere else —
# same tests, two execution modes
INTERPRET = jax.default_backend() != "tpu"


def _i8(*shape):
    return jnp.asarray(RNG.integers(-128, 128, size=shape), jnp.int8)


def _f32(*shape):
    return jnp.asarray(RNG.normal(size=shape), jnp.float32)


def _both(x, w, b=None, **kw):
    a = conv2d_ws(x, w, b, interpret=INTERPRET, **kw)
    p = conv2d_ws_pipe(x, w, b, interpret=INTERPRET, **kw)
    assert a.dtype == p.dtype and a.shape == p.shape
    np.testing.assert_array_equal(np.asarray(a), np.asarray(p))
    return a


# ---------------------------------------------------------------------------
# Bit-exactness vs the sequential kernel — deterministic hard cases
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("padding", ["VALID", "SAME", ((2, 1), (0, 2))])
def test_pipe_bit_exact_stride_padding(stride, padding):
    x, w = _i8(2, 11, 9, 8), _i8(3, 3, 8, 8)
    b = jnp.asarray(RNG.integers(-500, 500, (8,)), jnp.int32)
    _both(x, w, b, stride=stride, padding=padding,
          cin_banks=2, kout_banks=2)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_pipe_bit_exact_grouped(groups):
    """Dense, mid-grouped and depthwise (C=K=8, groups=8): the pipelined
    kernel's HBM slices must carry the same per-group channel offsets as
    the sequential BlockSpec index maps."""
    c = k = 8
    x, w = _i8(1, 12, 10, c), _i8(3, 3, c // groups, k)
    cb, kb = ref.grouped_banks(c, k, groups)
    got = _both(x, w, stride=1, padding="SAME", groups=groups,
                cin_banks=cb, kout_banks=kb)
    want = ref.conv2d_ref_int8(x, w, padding="SAME", groups=groups)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# Shapes of the tap-folded compute body (``conv2d_ws.conv_slab``):
# (H, W, KH=KW, dilation, cin_banks, (h_tile, w_tile)); 0 = whole map.
FOLD_CASES = [
    pytest.param(9, 7, 3, 1, 2, (0, 0), id="w7"),
    pytest.param(14, 14, 3, 1, 4, (0, 0), id="w14-4banks"),
    pytest.param(12, 14, 2, 1, 2, (6, 0), id="2x2-tiled"),
    pytest.param(10, 14, 1, 1, 2, (0, 0), id="1x1"),
    pytest.param(14, 14, 3, 2, 2, (0, 0), id="dilation2"),
]


@pytest.mark.parametrize("h,w,kh,dilation,cin_banks,tile", [
    pytest.param(16, 16, 3, 1, 2, (8, 8), id="tiled8x8")] + FOLD_CASES)
def test_pipe_bit_exact_fused_epilogue_requant(h, w, kh, dilation,
                                               cin_banks, tile):
    """ReLU → 2×2 max-pool → requantize, at widths off the sublane tile,
    with 1, 4 and 9 taps, dilated and tiled: the epilogue runs on the
    ping-pong output buffer and its store overlaps the next tile, and
    both kernels equal the int8 reference chain bit for bit."""
    x, wt = _i8(2, h, w, 8), _i8(kh, kh, 8, 16)
    b = jnp.asarray(RNG.integers(-500, 500, (16,)), jnp.int32)
    kw = dict(stride=1, padding="SAME", relu=True, pool=True,
              dilation=dilation)
    out = _both(x, wt, b, out_scale=0.015, cin_banks=cin_banks,
                kout_banks=4, h_tile=tile[0], w_tile=tile[1], **kw)
    assert out.dtype == jnp.int8
    want = ref.conv2d_epilogue_ref(x, wt, b, out_scale=0.015, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("h,w,kh,dilation,cin_banks,tile", [
    pytest.param(13, 11, 3, 1, 2, (4, 8), id="tiled4x8")] + FOLD_CASES)
def test_pipe_bit_exact_float_accumulator(h, w, kh, dilation, cin_banks,
                                          tile):
    """The f32 accumulator path: bitwise equality requires the pipelined
    kernel to accumulate in exactly the sequential order (co-major, each
    slab's taps in one contraction) — allclose would hide a
    reordering."""
    x, wt, b = _f32(1, h, w, 8), _f32(kh, kh, 8, 8), _f32(8)
    _both(x, wt, b, stride=1, padding="SAME", relu=True, dilation=dilation,
          cin_banks=cin_banks, kout_banks=2, h_tile=tile[0],
          w_tile=tile[1])


def test_pipe_bit_exact_1x1_pointwise():
    x, w = _i8(1, 9, 9, 16), _i8(1, 1, 16, 16)
    _both(x, w, cin_banks=4, kout_banks=4)


def test_pipe_single_slab_degenerate():
    """cin_banks = kout_banks = 1, one tile: a 1-slab pipeline is pure
    fill + drain — the warm-up/prefetch/drain protocol must not deadlock
    or read a buffer that was never filled."""
    x, w = _i8(1, 6, 6, 4), _i8(3, 3, 4, 4)
    _both(x, w, cin_banks=1, kout_banks=1)


def test_pipe_odd_cin_banks_slot_parity():
    """cin_banks odd (here 3): consecutive grid steps start on OPPOSITE
    ping-pong slots, so any slot math keyed to co alone (instead of the
    global slab index) would clobber the buffer in flight."""
    x, w = _i8(1, 10, 10, 12), _i8(3, 3, 12, 8)
    _both(x, w, cin_banks=3, kout_banks=2, h_tile=4, w_tile=4)


def test_pipe_through_ops_dispatch():
    """ops.conv2d(pipelined=True) routes to the pipe kernel on both the
    int8 and the differentiable float path, bit-equal to the default."""
    x, w = _i8(1, 10, 10, 8), _i8(3, 3, 8, 8)
    np.testing.assert_array_equal(
        np.asarray(ops.conv2d(x, w, pipelined=True)),
        np.asarray(ops.conv2d(x, w)))
    xf, wf = _f32(1, 10, 10, 8), _f32(3, 3, 8, 8)
    np.testing.assert_array_equal(
        np.asarray(ops.conv2d(xf, wf, relu=True, pipelined=True)),
        np.asarray(ops.conv2d(xf, wf, relu=True)))


def test_pipe_float_path_differentiable():
    """The pipelined float path carries the same custom VJP: gradients
    are bitwise those of the sequential path (the VJP rules recompute
    residuals sequentially — legal because the kernels are bit-exact)."""
    xf, wf, bf = _f32(1, 8, 8, 4), _f32(3, 3, 4, 4), _f32(4)

    def loss(pipelined):
        def f(x, w, b):
            y = ops.conv2d(x, w, b, relu=True, pool=True,
                           cin_banks=2, kout_banks=2, pipelined=pipelined)
            return jnp.sum(y * y)
        return jax.grad(f, argnums=(0, 1, 2))(xf, wf, bf)

    for g_pipe, g_seq in zip(loss(True), loss(False)):
        np.testing.assert_array_equal(np.asarray(g_pipe), np.asarray(g_seq))


# ---------------------------------------------------------------------------
# Hypothesis sweep (guarded import, same pattern as test_tiling.py)
# ---------------------------------------------------------------------------


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def pipe_case(draw):
        stride = draw(st.sampled_from([1, 2]))
        padding = draw(st.sampled_from(
            ["VALID", "SAME", ((draw(st.integers(0, 2)),
                                draw(st.integers(0, 2))),
                               (draw(st.integers(0, 2)),
                                draw(st.integers(0, 2))))]))
        groups = draw(st.sampled_from([1, 2, 8]))     # dense / mid / depthwise
        epilogue = draw(st.sampled_from(["none", "relu", "relu_pool"]))
        requant = draw(st.booleans())
        tiled = draw(st.booleans())
        h = draw(st.integers(8, 14))
        w = draw(st.integers(8, 14))
        seed = draw(st.integers(0, 2**31 - 1))
        return stride, padding, groups, epilogue, requant, tiled, h, w, seed

    @given(pipe_case())
    @settings(max_examples=25, deadline=None)
    def test_pipe_bit_exact_property(case):
        """Pipelined == sequential, bit-exact, across the full
        stride × padding × epilogue × groups × tiling space."""
        stride, padding, groups, epi, requant, tiled, h, w, seed = case
        c = k = 8
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.integers(-128, 128, (1, h, w, c)), jnp.int8)
        wt = jnp.asarray(rng.integers(-128, 128, (3, 3, c // groups, k)),
                         jnp.int8)
        b = jnp.asarray(rng.integers(-500, 500, (k,)), jnp.int32)
        oh, ow = ref.conv_out_shape(h, w, 3, 3, stride, padding)
        if oh < 1 or ow < 1:
            padding = "SAME"
            oh, ow = ref.conv_out_shape(h, w, 3, 3, stride, padding)
        pool = epi == "relu_pool" and oh >= 2 and ow >= 2
        cb, kb = ref.grouped_banks(c, k, groups)
        kw = dict(stride=stride, padding=padding, groups=groups,
                  cin_banks=cb, kout_banks=kb, relu=epi != "none",
                  pool=pool, out_scale=0.02 if requant else None)
        if tiled:
            ph, pw = (oh // 2, ow // 2) if pool else (oh, ow)
            if ph >= 2 and pw >= 2:
                kw["h_tile"] = 2 if pool else max(1, ph // 2)
                kw["w_tile"] = 2 if pool else max(1, pw // 2)
        _both(x, wt, b, **kw)

    @given(st.integers(8, 320), st.integers(8, 320),
           st.sampled_from([8, 16, 64]), st.sampled_from([8, 16, 64]),
           st.sampled_from([1, 2, 8]), st.booleans(),
           st.sampled_from([1 << 18, 1 << 20, 1 << 22]))
    @settings(max_examples=40, deadline=None)
    def test_pipe_vmem_accounting_property(h, w, c, k, groups, pool,
                                           budget):
        """The ping-pong working set never exceeds the budget the planner
        promised: ``working_set_bytes`` (whose ×2 term IS the two
        ping-pong slots, counted as laid out in VMEM) fits whenever the
        plan claims to, the kernel is the plan's slab rule, and budget
        degradation still yields legal plans — dense and grouped."""
        if k % groups:
            k = groups * max(1, k // groups)
        oh, ow = ref.conv_out_shape(h, w, 3, 3, 1, "SAME")
        if pool and (oh < 2 or ow < 2):
            pool = False
        cb, kb = banking.grouped_banks(c, k, groups)
        p = plan_tiles(h, w, c, k, stride=1, padding="SAME", pool=pool,
                       groups=groups, in_bytes=1, out_bytes=1,
                       cin_banks=cb, kout_banks=kb, vmem_budget=budget)
        assert p.pipelined == (p.n_slabs > 1)
        assert p.n_slabs == p.n_tiles * p.cin_banks * p.kout_banks
        lay = banking.laid_out_bytes
        # explicit ping-pong buffers: 2 input + 2 weight + 2 output
        # (+ bias/scale) slots, then the compute body's scratch and
        # values, each as laid out in VMEM — first principles, must
        # equal the planner's promise.  A width off the 8-row sublane
        # tile folds the taps: accumulator over the window's padded
        # width, tap patch (nine 128-lane column blocks), window and
        # its flattened copy, one tap's rows, two accumulator-sized
        # values; otherwise per-tap dots: the accumulator, window,
        # one tap slice and four accumulator-sized values.
        cgb, kgb = c // groups // p.cin_banks, k // p.kout_banks
        th, tw = p.h_tile, p.w_tile
        win = lay((p.in_h_tile if p.tiled else h + 2, w + 2, cgb), 1)
        pth, ptw = (th // 2, tw // 2) if pool else (th, tw)
        pingpong = 2 * (win + lay((3, 3, cgb, kgb), 1)
                        + lay((pth, ptw, kgb), 1)
                        + 2 * lay((1, kgb), 4))
        if tw % 8:
            wide = -(-(w + 2) // 8) * 8
            rows = (th - 1) * wide + tw
            acc = lay((th * wide, kgb), 4)
            pingpong += (acc + lay((rows, 9 * 128), 1) + 2 * win
                         + lay((rows, cgb), 1) + 2 * acc)
        else:
            acc = lay((th * tw, kgb), 4)
            pingpong += acc + win + lay((th * tw, cgb), 1) + 4 * acc
        assert p.working_set_bytes == pingpong
        assert p.fits_vmem == (pingpong <= budget)
        # legality under degradation, dense and grouped
        assert (c // groups) % p.cin_banks == 0
        assert k % p.kout_banks == 0 and p.kout_banks % groups == 0
        assert p.w_tile == p.out_w
        if groups == 1:
            assert ref.lane_legal_banks(c, p.cin_banks)
            assert ref.lane_legal_banks(k, p.kout_banks)
        assert p.n_h_tiles * p.h_tile >= p.out_h
        assert p.n_w_tiles * p.w_tile >= p.out_w
        if pool:
            assert p.h_tile % 2 == 0 and p.w_tile % 2 == 0


# ---------------------------------------------------------------------------
# Whole networks: every scheduler mode, kernel choice from the plan
# ---------------------------------------------------------------------------


def _net_setup(make):
    plan = make()
    rng = np.random.default_rng(3)
    params = plan.init_params(rng)
    xf = jnp.asarray(rng.normal(size=(2,) + plan.input_shape), jnp.float32)
    qnet = network.quantize_network(plan, params, xf)
    x8 = jnp.clip(jnp.round(xf / qnet.in_scale), -128, 127).astype(jnp.int8)
    return qnet, x8


@pytest.mark.parametrize("mode", ["batch", "kout", "spatial"])
def test_pipelined_network_bit_exact_all_scheduler_modes(mode, monkeypatch):
    """make_int8_program with every conv forced onto conv2d_ws_pipe is
    bit-identical to the all-sequential compile under all three
    scheduler modes — the kernel choice must survive the shard-plan
    rewrites (kout re-banking, spatial slicing)."""
    qnet, x8 = _net_setup(network.mobilenet_small)
    outs = []
    for forced in (False, True):
        monkeypatch.setattr(banking.TilePlan, "pipelined",
                            property(lambda self, v=forced: v))
        sched = scheduler.MultiCoreScheduler(
            scheduler.SchedulerConfig(n_cores=2, mode=mode))
        name = "pallas"
        if mode != "batch":
            sb = sched.shard_backend("pallas")
            register_backend(sb)
            name = sb.name
        program = network.make_int8_program(
            qnet, ConvCoreConfig(backend=name, int8=True))
        outs.append(sched.run(program, x8))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


def test_auto_kernel_network_matches_ref():
    """The default compile (each layer's plan picks its kernel, so the
    variants mix per layer) stays bit-exact against the ref backend."""
    qnet, x8 = _net_setup(network.mobilenet_small)
    a = network.make_int8_program(
        qnet, ConvCoreConfig(backend="pallas", int8=True))(x8)
    b = network.make_int8_program(
        qnet, ConvCoreConfig(backend="ref", int8=True))(x8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# The slab rule and its §5.2 pricing (no kernels: pure cost model — fast)
# ---------------------------------------------------------------------------


def test_paper_anchors_untouched():
    """The new pipeline layer must not drift §5.2: 3,154,176 psums,
    0.224 / 4.48 GOPS exact (also asserted standalone in CI)."""
    refnum = perfmodel.paper_reference_numbers()
    assert refnum["psums"] == 3_154_176
    assert refnum["gops_1core"] == pytest.approx(0.224, rel=1e-3)
    assert refnum["gops_20cores"] == pytest.approx(4.48, rel=1e-2)


def test_pipeline_estimate_model_identities():
    """fill + steady-state + drain from first principles: with D = n·d
    and C = n·c exactly, pipelined = d + (n−1)·max(d,c) + c + n·overhead,
    sequential = D + C, and a 1-slab pipe is pure fill+drain+overhead."""
    plan = plan_tiles(32, 32, 8, 8, in_bytes=1, out_bytes=1)
    n = plan.n_slabs
    psums = perfmodel.psum_count(32, 32, 8, 8)
    est = perfmodel.pipeline_estimate(plan, psums)
    d = -(-est["dma_cycles"] // n)
    c = -(-est["compute_cycles"] // n)
    assert est["n_slabs"] == n
    assert est["sequential_cycles"] == est["dma_cycles"] + est["compute_cycles"]
    assert est["pipelined_cycles"] == (
        d + (n - 1) * max(d, c) + c
        + n * perfmodel.PIPELINE_OVERHEAD_CYCLES)
    assert not plan.pipelined
    assert est["cycles"] == est["sequential_cycles"]
    # perfect overlap bound: pipelining can never beat the slower phase
    assert est["pipelined_cycles"] >= max(est["dma_cycles"],
                                          est["compute_cycles"])


def test_predictor_marks_depthwise_dma_bound_profitable():
    """On every MobileNet zoo plan, each depthwise layer the perf model
    flags dma_bound_board sweeps one slab per group, so the slab rule
    pipelines it, and the one-core model prices that as a gain."""
    for make in (network.mobilenet_small, network.mobilenet_v2ish):
        plan = make()
        tps = plan.tile_plans()
        by_name = dict(zip(plan.node_names(), tps))
        rep = perfmodel.network_report(plan.psum_table(), tile_plans=tps)
        geoms = dict(zip(plan.node_names(), plan.conv_geometries()))
        dw_rows = [r for r in rep["layers"]
                   if geoms.get(r["name"]) and geoms[r["name"]][1] > 1
                   and r.get("dma_bound_board")]
        assert dw_rows, "zoo plan must contain DMA-bound depthwise layers"
        for r in dw_rows:
            assert by_name[r["name"]].n_slabs > 1, r
            assert r["pipelined"], r
            assert r["pipeline_speedup"] > 1.0, r
        assert rep["pipelined_layers"] >= len(dw_rows)


def test_predictor_leaves_tiny_layers_sequential():
    """A one-slab sweep has no next slab to prefetch, so it stays on the
    sequential kernel — the rule makes a real choice, not a constant
    one."""
    tiny = plan_tiles(6, 6, 4, 4)
    assert tiny.n_slabs == 1 and not tiny.pipelined
    # one lane-legal bank each way: the slabs come from row tiles
    big = plan_tiles(256, 256, 16, 16)
    assert big.cin_banks == big.kout_banks == 1
    assert big.n_slabs == big.n_tiles > 1 and big.pipelined


def test_network_report_prices_chosen_variant():
    """Priced rows expose both variants and charge the one each row's
    plan runs (``TilePlan.pipelined``, the slab rule); against an
    all-sequential pricing of the same plans the one-core total can only
    go down.

    On the 20-core board compute divides by 20 and the shared DMA
    interface does not, so a depthwise row — one slab per group — has
    almost nothing left to overlap and loses to the 16-cycle per-slab
    overhead (mobilenet_small d1: 8 slabs, 645 board cycles sequential,
    727 pipelined).  Dense rows gain on the board: vgg_imagenet, whose
    pipelined rows are all dense, keeps the full-board total at or below
    the sequential one.  mobilenet_small's pointwise rows are one
    lane-legal bank (one slab) and stay sequential, so nothing offsets
    its depthwise losses: its board total rises by exactly their sum."""
    board = perfmodel.IPCoreConfig(ip_cores=20)
    board_losers = {"mobilenet_small": {"d1", "d2", "d3"},
                    "vgg_imagenet": set()}
    for name, losers in board_losers.items():
        plan = getattr(network, name)()
        table, plans = plan.psum_table(), plan.tile_plans()
        auto = perfmodel.network_report(table, tile_plans=plans)
        assert auto["pipelined_layers"] > 0
        seq_total = seq_board = board_delta = 0
        lost = set()
        for r, tp in zip(auto["layers"], plans):
            if "pipelined" not in r:
                seq_total += r["cycles"]
                seq_board += perfmodel.cycles(r["psums"], board)
                continue
            assert r["pipelined"] == tp.pipelined == (tp.n_slabs > 1)
            chosen = (r["cycles_pipelined"] if r["pipelined"]
                      else r["cycles_sequential"])
            est = perfmodel.pipeline_estimate(tp, r["psums"], board)
            if r["psums"]:
                assert r["cycles"] == chosen
                assert chosen == min(r["cycles_pipelined"],
                                     r["cycles_sequential"])
                seq_total += r["cycles_sequential"]
                seq_board += est["sequential_cycles"]
            else:
                seq_total += r["cycles"]
                seq_board += r["dma_cycles"]
            if r["psums"] and r["pipelined"]:
                delta = est["pipelined_cycles"] - est["sequential_cycles"]
                board_delta += delta
                if delta > 0:
                    # depthwise: one kout bank, hence one slab, per group
                    assert tp.groups > 1 and tp.kout_banks == tp.groups
                    lost.add(r["name"])
            # both estimates are real costs: never below the DMA time
            assert r["cycles_sequential"] >= r["dma_cycles"]
            assert r["cycles_pipelined"] >= r["dma_cycles"]
        assert lost == losers, name
        assert auto["cycles"] < seq_total, name
        assert auto["full_board"]["cycles"] - seq_board == board_delta, name
        if not losers:
            assert auto["full_board"]["cycles"] <= seq_board


# ---------------------------------------------------------------------------
# The slab rule: TilePlan.pipelined ⇔ n_slabs > 1
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("geom, n_slabs", [
    # (h, w, c, k, th, cin_banks, kout_banks, groups)
    ((8, 8, 8, 8, 8, 1, 1, 1), 1),            # one slab
    ((16, 16, 8, 8, 4, 1, 1, 1), 4),          # four row tiles
    ((8, 8, 256, 128, 8, 2, 1, 1), 2),        # two cin banks
    ((8, 8, 128, 256, 8, 1, 2, 1), 2),        # two kout banks
    ((8, 8, 8, 8, 8, 1, 2, 2), 2),            # grouped, a kout bank per group
], ids=["one_slab", "row_tiles", "two_cin_banks", "two_kout_banks",
        "grouped_two_kout_banks"])
def test_slab_rule(geom, n_slabs):
    h, w, c, k, th, cb, kb, groups = geom
    plan = banking.tile_plan(h, w, c, k, 3, 3, th, w, cb, kb,
                             padding="SAME", groups=groups)
    assert plan.n_slabs == plan.n_tiles * cb * kb == n_slabs
    assert plan.pipelined == (n_slabs > 1)


_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@functools.lru_cache(maxsize=None)
def _harness_plan(name):
    """The benchmark configuration's plan, as its harness builds it, and
    the tile plans its int8 program runs."""
    if _ROOT not in sys.path:
        sys.path.insert(0, _ROOT)
    from perfbench.harness.model import build_plan
    with open(os.path.join(_ROOT, "perfbench", "configs",
                           f"{name}.json")) as f:
        plan = build_plan(json.load(f))
    return plan, network.program_tile_plans(plan, ConvCoreConfig(int8=True))


_RESNET50_ONE_SLAB = ("res2a_1", "res2a_2", "res2b_2", "res2c_2", "res3a_2",
                      "res3b_2", "res3c_2", "res3d_2")


@pytest.mark.parametrize("name, sequential", [
    ("vgg16", ()), ("unet", ()), ("resnet50", _RESNET50_ONE_SLAB)],
    ids=["vgg16", "unet", "resnet50"])
def test_benchmark_configs_sequential_layers(name, sequential):
    """Which conv layers of the benchmark's configurations run on
    conv2d_ws: exactly the ones whose plan sweeps a single slab."""
    plan, tps = _harness_plan(name)
    convs = [(n, tp) for n, tp in zip(plan.node_names(), tps)
             if tp is not None]
    assert convs
    assert tuple(n for n, tp in convs if not tp.pipelined) == sequential
    assert all(tp.pipelined == (tp.n_slabs > 1) for _, tp in convs)


@pytest.mark.parametrize("layer", ["res2a_1", "res2a_2", "res3a_2",
                                   "res3b_2"])
def test_resnet50_one_slab_layers_kernel_parity(layer):
    """Both kernels give the same int8 map, bit for bit, on ResNet-50's
    one-slab layers at their own shapes and plans (batch 1, fused ReLU
    and requantize)."""
    plan, tps = _harness_plan("resnet50")
    names = plan.node_names()
    i = names.index(layer)
    sp, tp = plan.layers[i], tps[i]
    src = plan.resolved_inputs()[i][0]
    h, w, c = plan.activation_shapes()[src]
    assert tp.n_slabs == 1
    rng = np.random.default_rng(i)
    x = jnp.asarray(rng.integers(-128, 128, (1, h, w, c)), jnp.int8)
    wt = jnp.asarray(rng.integers(-128, 128,
                                  (*sp.kernel, c, sp.features)), jnp.int8)
    b = jnp.asarray(rng.integers(-2048, 2048, (sp.features,)), jnp.int32)
    run = functools.partial(
        ops.conv2d, x, wt, b, stride=sp.stride, padding=sp.padding,
        cin_banks=tp.cin_banks, kout_banks=tp.kout_banks, relu=sp.relu,
        out_scale=jnp.float32(2.0 ** -12))
    seq, pipe = run(pipelined=False), run(pipelined=True)
    assert seq.dtype == jnp.int8 and seq.shape == (1, tp.out_h, tp.out_w,
                                                   sp.features)
    np.testing.assert_array_equal(np.asarray(seq), np.asarray(pipe))
