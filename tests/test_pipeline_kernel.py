"""The explicit double-buffered DMA conv pipeline (kernels/conv2d_ws_pipe)
and its planner/cost-model contract:

* bit-exactness vs conv2d_ws across stride × padding × epilogue × groups ×
  tiling (deterministic hard cases + a hypothesis sweep), on the int8 AND
  float accumulator paths, whole networks under every scheduler mode;
* VMEM accounting: the ping-pong working set IS the working set
  ``plan_tiles`` already budgets (the ×2 double-buffer term), so the
  ``pipelined`` choice never changes whether a plan fits, and budget
  degradation still yields legal plans, dense and grouped;
* the crossover predictor: §5.2 anchors untouched, depthwise
  ``dma_bound_board`` layers marked profitable, tiny layers left
  sequential, and ``network_report`` pricing consistent both ways.

On a TPU host these tests compile natively (the CI smoke lane);
elsewhere they run in interpret mode.
"""

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


def test_pipe_bit_exact_fused_epilogue_requant():
    """ReLU → 2×2 max-pool → requantize, tiled: the epilogue runs on the
    ping-pong output buffer and its store overlaps the next tile."""
    x, w = _i8(2, 16, 16, 8), _i8(3, 3, 8, 16)
    b = jnp.asarray(RNG.integers(-500, 500, (16,)), jnp.int32)
    out = _both(x, w, b, out_scale=0.015, stride=1, padding="SAME",
                relu=True, pool=True, cin_banks=2, kout_banks=4,
                h_tile=8, w_tile=8)
    assert out.dtype == jnp.int8


def test_pipe_bit_exact_float_accumulator():
    """The f32 accumulator path: bitwise equality requires the pipelined
    kernel to accumulate in exactly the sequential order (co-major, then
    the KH×KW taps) — allclose would hide a reordering."""
    x, w, b = _f32(1, 13, 11, 8), _f32(3, 3, 8, 8), _f32(8)
    _both(x, w, b, stride=1, padding="SAME", relu=True,
          cin_banks=2, kout_banks=2, h_tile=4, w_tile=8)


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
        plan claims to, the ``pipelined`` flag changes no byte counts,
        and budget degradation still yields legal plans — dense and
        grouped."""
        if k % groups:
            k = groups * max(1, k // groups)
        oh, ow = ref.conv_out_shape(h, w, 3, 3, 1, "SAME")
        if pool and (oh < 2 or ow < 2):
            pool = False
        cb, kb = banking.grouped_banks(c, k, groups)
        plans = {
            mode: plan_tiles(h, w, c, k, stride=1, padding="SAME",
                             pool=pool, groups=groups, in_bytes=1,
                             out_bytes=1, cin_banks=cb, kout_banks=kb,
                             vmem_budget=budget, kernel=mode)
            for mode in ("sequential", "pipelined", "auto")
        }
        seq, pipe = plans["sequential"], plans["pipelined"]
        # identical geometry and bytes — only the kernel choice differs
        assert seq.working_set_bytes == pipe.working_set_bytes
        assert (seq.h_tile, seq.w_tile, seq.cin_banks, seq.kout_banks) \
            == (pipe.h_tile, pipe.w_tile, pipe.cin_banks, pipe.kout_banks)
        assert not seq.pipelined and pipe.pipelined
        lay = banking.laid_out_bytes
        for p in plans.values():
            # explicit ping-pong buffers: 2 input + 2 weight + 2 output
            # (+ bias/scale) slots, the single accumulator and the kernel
            # body's values, each as laid out in VMEM — first principles,
            # must equal the planner's promise
            cgb, kgb = c // groups // p.cin_banks, k // p.kout_banks
            th, tw = p.h_tile, p.w_tile
            win = lay((p.in_h_tile if p.tiled else h + 2, w + 2, cgb), 1)
            acc = lay((th * tw, kgb), 4)
            pth, ptw = (th // 2, tw // 2) if pool else (th, tw)
            pingpong = (2 * (win + lay((3, 3, cgb, kgb), 1)
                             + lay((pth, ptw, kgb), 1)
                             + 2 * lay((1, kgb), 4))
                        + lay((th, tw, kgb), 4)
                        + win + lay((th * tw, cgb), 1) + 4 * acc)
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
# Whole networks: every scheduler mode, planner-auto kernel choice
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
def test_pipelined_network_bit_exact_all_scheduler_modes(mode):
    """make_int8_program with kernel="pipelined" (every conv forced onto
    conv2d_ws_pipe) is bit-identical to the sequential compile under all
    three scheduler modes — the TilePlan.pipelined flag must survive the
    shard-plan rewrites (kout re-banking, spatial slicing)."""
    qnet, x8 = _net_setup(network.mobilenet_small)
    outs = []
    for kernel in ("sequential", "pipelined"):
        sched = scheduler.MultiCoreScheduler(
            scheduler.SchedulerConfig(n_cores=2, mode=mode))
        name = "pallas"
        if mode != "batch":
            sb = sched.shard_backend("pallas")
            register_backend(sb)
            name = sb.name
        program = network.make_int8_program(
            qnet, ConvCoreConfig(backend=name, int8=True, kernel=kernel))
        outs.append(sched.run(program, x8))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))


def test_auto_kernel_network_matches_ref():
    """The default compile (kernel="auto" — the planner mixes variants
    per layer) stays bit-exact against the ref backend."""
    qnet, x8 = _net_setup(network.mobilenet_small)
    a = network.make_int8_program(
        qnet, ConvCoreConfig(backend="pallas", int8=True))(x8)
    b = network.make_int8_program(
        qnet, ConvCoreConfig(backend="ref", int8=True))(x8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# The crossover predictor (no kernels: pure cost model — fast)
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
    plan = plan_tiles(32, 32, 8, 8, in_bytes=1, out_bytes=1,
                      kernel="sequential")
    n = perfmodel.pipeline_slabs(plan)
    psums = perfmodel.psum_count(32, 32, 8, 8)
    est = perfmodel.pipeline_estimate(plan, psums)
    d = -(-est["dma_cycles"] // n)
    c = -(-est["compute_cycles"] // n)
    assert est["n_slabs"] == n
    assert est["sequential_cycles"] == est["dma_cycles"] + est["compute_cycles"]
    assert est["pipelined_cycles"] == (
        d + (n - 1) * max(d, c) + c
        + n * perfmodel.PIPELINE_OVERHEAD_CYCLES)
    assert est["profitable"] == (
        est["pipelined_cycles"] < est["sequential_cycles"])
    # perfect overlap bound: pipelining can never beat the slower phase
    assert est["pipelined_cycles"] >= max(est["dma_cycles"],
                                          est["compute_cycles"])


def test_predictor_marks_depthwise_dma_bound_profitable():
    """Acceptance: on every MobileNet zoo plan, each depthwise layer the
    perf model flags dma_bound_board is marked pipelined-profitable (the
    DMA-floor diagnosis converted into recovered throughput)."""
    for make in (network.mobilenet_small, network.mobilenet_v2ish):
        plan = make()
        tps = plan.tile_plans()           # kernel="auto"
        rep = perfmodel.network_report(plan.psum_table(), tile_plans=tps)
        geoms = dict(zip(plan.node_names(), plan.conv_geometries()))
        dw_rows = [r for r in rep["layers"]
                   if geoms.get(r["name"]) and geoms[r["name"]][1] > 1
                   and r.get("dma_bound_board")]
        assert dw_rows, "zoo plan must contain DMA-bound depthwise layers"
        for r in dw_rows:
            assert r["pipelined"], r
            assert r["pipeline_speedup"] > 1.0, r
        assert rep["pipelined_layers"] >= len(dw_rows)


def test_predictor_leaves_tiny_layers_sequential():
    """Per-slab protocol overhead keeps the pipeline off layers with
    almost nothing to overlap — auto must make a real choice, not a
    constant one."""
    tiny = plan_tiles(6, 6, 4, 4, kernel="auto")
    assert not tiny.pipelined
    # one lane-legal bank each way: the slabs come from row tiles
    big = plan_tiles(256, 256, 16, 16, kernel="auto")
    assert big.n_tiles > 1 and big.pipelined


def test_network_report_prices_chosen_variant():
    """Priced rows expose both variants and charge the chosen one; the
    sequential total can only go down when the planner pipelines.

    The planner ranks the variants on one core's cycles.  On the 20-core
    board compute divides by 20 and the shared DMA interface does not,
    so a depthwise row — one slab per group — has almost nothing left to
    overlap and loses to the 16-cycle per-slab overhead (mobilenet_small
    d1: 8 slabs, 645 board cycles sequential, 727 pipelined).  Dense rows
    gain on the board: vgg_imagenet, whose pipelined rows are all dense,
    keeps the full-board total at or below the sequential one.
    mobilenet_small's pointwise rows are one lane-legal bank (one slab)
    and stay sequential, so nothing offsets its depthwise losses: its
    board total rises by exactly their sum."""
    board = perfmodel.IPCoreConfig(ip_cores=20)
    board_losers = {"mobilenet_small": {"d1", "d2", "d3"},
                    "vgg_imagenet": set()}
    for name, losers in board_losers.items():
        plan = getattr(network, name)()
        table, plans = plan.psum_table(), plan.tile_plans()
        auto = perfmodel.network_report(table, tile_plans=plans)
        seq = perfmodel.network_report(
            table, tile_plans=plan.tile_plans(kernel="sequential"))
        assert auto["pipelined_layers"] > 0 and seq["pipelined_layers"] == 0
        assert auto["cycles"] < seq["cycles"]
        board_delta, lost = 0, set()
        for r, tp in zip(auto["layers"], plans):
            if "pipelined" not in r:
                continue
            chosen = (r["cycles_pipelined"] if r["pipelined"]
                      else r["cycles_sequential"])
            if r["psums"]:
                assert r["cycles"] == chosen
                assert chosen == min(r["cycles_pipelined"],
                                     r["cycles_sequential"])
            if r["psums"] and r["pipelined"]:
                est = perfmodel.pipeline_estimate(tp, r["psums"], board)
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
        assert (auto["full_board"]["cycles"] - seq["full_board"]["cycles"]
                == board_delta), name
        if not losers:
            assert auto["full_board"]["cycles"] <= seq["full_board"]["cycles"]


def test_forced_kernel_modes():
    p_seq = plan_tiles(32, 32, 8, 8, kernel="sequential")
    p_pipe = plan_tiles(32, 32, 8, 8, kernel="pipelined")
    assert not p_seq.pipelined and p_pipe.pipelined
    with pytest.raises(ValueError):
        plan_tiles(32, 32, 8, 8, kernel="bogus")
