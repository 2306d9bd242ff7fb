"""Network-level executor: whole CNNs through the layer-at-a-time IP core.

The paper's IP core "can process a convolutional layer at a time" (§4.2);
running a network on the FPGA means the host sequences layer passes, with
the output BRAMs of one pass becoming the image BRAMs of the next.  This
module is that sequencer as a compiler: a ``NetworkPlan`` — a **DAG** of
conv / pool / flatten / dense ``LayerSpec`` nodes plus ``add``/``concat``
merge nodes — is turned into one jitted multi-layer program over a
``Backend`` (core/convcore.py).

Graph topology: every node may name its producer(s) (``inputs``; empty
means "the previous layer", the straight-line default), so ResNet-class
skip connections and branch-merge topologies express directly.  The
``layers`` tuple must already be topologically ordered (inputs precede
consumers) — one left-to-right sweep IS a topological schedule, which is
also the hardware truth: the single layer-at-a-time core runs parallel
branches serially, the host just sequences the passes.

Layer-to-layer int8 chaining (the production path): ``quantize_network``
calibrates per-layer activation scales from a float forward pass, quantizes
weights/biases (per-tensor or per-output-channel — ``per_channel=True``
yields [K] scale vectors the fused epilogue broadcasts), and computes the
*requantization scale* of each layer (``s_in·s_w / s_out`` —
core/quantize.requant_scale).  The compiled int8 program then keeps every
inter-layer feature map in int8: the fused kernel epilogue (ReLU → pool →
requantize) writes the next layer's int8 input directly, so nothing
round-trips HBM in int32 — the FPGA post-processing idiom at network scale.

Residual adds stay on that int8 story: a skip add is only exact when both
branches land on the same int8 grid, so ``quantize_network`` calibrates a
shared output scale per merge node and emits per-branch requant scales
(``s_branch / s_out`` — quantize.branch_requant_scale) that align the skip
path and the conv path onto the shared grid.  The merge itself is then a
pure saturating int8 add (kernels/ref.add_requant_ref) — the FPGA
output-BRAM-crossbar idiom, no int32 round-trip.

Spatial tiling: ``make_int8_program`` computes a per-layer
``banking.TilePlan`` (``NetworkPlan.tile_plans``), so conv layers whose
whole-map working set exceeds the VMEM budget stream through halo'd H/W
tiles — VGG-small at 64×64+, the ImageNet-scale ``vgg_imagenet`` demo,
and the segmentation-scale ``large_map`` plan all compile unchanged.

Paper → TPU mapping of the replicated-IP-core mode (full-board 4.48 GOPS):
core/scheduler.py shards a compiled program across devices (one IP core ↔
one device) or vmapped virtual cores; core/perfmodel.network_report sums
the §5.2 cycle model over the plan's nodes, including the 20-core
configuration (branches serialize on the single core, so the DAG's cost
is still the sum of its nodes).

Training: core/training.py trains the float shadow of any plan through
the WS kernels' custom VJPs (QAT-aware), and the trained parameters feed
straight back into ``quantize_network`` → ``make_int8_program``;
:meth:`NetworkPlan.train_report` prices a train step on the §5.2 model.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import banking, perfmodel
from repro.core.convcore import ConvCoreConfig, get_backend
from repro.core.quantize import (act_scale_from_calibration,
                                 branch_requant_scale, quantize_symmetric,
                                 requant_scale)
from repro.kernels import ref

# ---------------------------------------------------------------------------
# Layer graph
# ---------------------------------------------------------------------------

INPUT = "input"          # reserved node name: the network input
DEPTHWISE = -1           # LayerSpec.groups sentinel: groups = cin
PARAM_KINDS = ("conv", "conv_transpose", "dense")   # nodes that own weights


@dataclass(frozen=True)
class LayerSpec:
    """One node of a CNN graph.

    kind: "conv" | "conv_transpose" | "pool" | "avgpool" | "globalpool" |
    "flatten" | "dense" | "add" | "concat".  ``pool=True`` on a conv
    layer fuses the 2×2/2 max-pool into the kernel epilogue (one HBM
    round-trip); standalone "pool" / "avgpool" layers are the unfused
    fallbacks, and "globalpool" is the global average pool
    ([N,H,W,C] → [N,C]) that lets classifier heads skip the flatten +
    giant-dense pattern.

    ``dilation`` (conv kinds) spaces the kernel taps by inserting
    ``dilation−1`` zeros between them (rhs dilation — the dilated-context
    trick that widens receptive fields without shrinking the map).
    "conv_transpose" is the learned-upsampling node (lhs zero-insertion:
    output grows ~stride×); its weights share the forward conv layout
    [KH,KW,C/groups,K] and it lowers onto the SAME weight-stationary
    kernels via the stride-1 equivalent conv
    (kernels/conv2d_ws_trans.py), so the int8 epilogue contract
    (ReLU → pool → requantize) carries over unchanged.

    ``groups`` (conv only) selects grouped channel contraction: 1 = dense,
    ``DEPTHWISE`` (−1) resolves to the node's input channel count at walk
    time — the MobileNet depthwise case, where ``features`` may stay 0 to
    default to "same width as the input".  ``conv_geometry`` is the single
    resolver every shape/cost/compile walk shares.

    ``name`` labels the node so later layers can reference it (default
    ``f"{kind}{index}"``); ``inputs`` names the producer node(s) — empty
    means "the previous layer" (the straight-line default) and the
    reserved name "input" is the network input.  "add" is the residual
    merge (exactly two branches of identical shape, optional fused ReLU);
    "concat" stacks ≥2 branches along the channel axis."""
    kind: str
    features: int = 0                      # conv: K; dense: output dim
    kernel: Tuple[int, int] = (3, 3)
    stride: int = 1
    padding: ref.Padding = "SAME"
    relu: bool = False
    pool: bool = False                     # conv only: fused 2×2 max-pool
    size: int = 2                          # "pool"/"avgpool": window/stride
    groups: int = 1                        # conv only: 1=dense, −1=depthwise
    dilation: int = 1                      # conv kinds: kernel-tap spacing
    name: Optional[str] = None             # node label for skip references
    inputs: Tuple[str, ...] = ()           # () → previous layer


def conv_geometry(sp: LayerSpec, cin: int,
                  name: str = "?") -> Tuple[int, int]:
    """Resolve a conv node's (features, groups) given its input channel
    count — the ONE place the DEPTHWISE sentinel and the grouped
    divisibility contract are interpreted, shared by every walk (shapes,
    params, psums, tile plans, the float oracle, the int8 compiler, the
    trainer) so they can never disagree."""
    groups = cin if sp.groups == DEPTHWISE else sp.groups
    features = sp.features if sp.features else (
        cin if sp.groups == DEPTHWISE else 0)
    if features <= 0:
        raise ValueError(f"node {name!r}: conv needs features > 0")
    if groups < 1 or cin % groups or features % groups:
        raise ValueError(
            f"node {name!r}: groups={groups} must divide both the input "
            f"channels C={cin} and the kernels K={features} "
            f"(groups == C is depthwise)")
    return features, groups


def _single(input: Optional[str]) -> Tuple[str, ...]:
    return () if input is None else (input,)


def conv(features: int, kernel: int = 3, stride: int = 1,
         padding: ref.Padding = "SAME", relu: bool = True,
         pool: bool = False, groups: int = 1, dilation: int = 1,
         name: Optional[str] = None,
         input: Optional[str] = None) -> LayerSpec:
    return LayerSpec("conv", features=features, kernel=(kernel, kernel),
                     stride=stride, padding=padding, relu=relu, pool=pool,
                     groups=groups, dilation=dilation, name=name,
                     inputs=_single(input))


def conv_transpose(features: int, kernel: int = 2, stride: int = 2,
                   padding: ref.Padding = "VALID", relu: bool = True,
                   pool: bool = False, groups: int = 1, dilation: int = 1,
                   name: Optional[str] = None,
                   input: Optional[str] = None) -> LayerSpec:
    """Transposed-conv (learned upsampling) node: output spatial size is
    ``(h−1)·stride + dilated_extent`` under VALID padding and ``h·stride``
    under SAME — the 2×2/stride-2 default exactly doubles the map, the
    U-Net decoder idiom.  Weights are forward-conv layout
    [KH,KW,C/groups,K]."""
    return LayerSpec("conv_transpose", features=features,
                     kernel=(kernel, kernel), stride=stride, padding=padding,
                     relu=relu, pool=pool, groups=groups, dilation=dilation,
                     name=name, inputs=_single(input))


def depthwise(kernel: int = 3, stride: int = 1,
              padding: ref.Padding = "SAME", relu: bool = True,
              pool: bool = False, features: int = 0,
              name: Optional[str] = None,
              input: Optional[str] = None) -> LayerSpec:
    """Depthwise conv node (groups == input channels): each channel is
    filtered by its own spatial kernel — the MobileNet workload family's
    per-channel half of a depthwise-separable block.  ``features``
    defaults to the input width (multiplier 1); a multiple of it selects
    a channel multiplier."""
    return LayerSpec("conv", features=features, kernel=(kernel, kernel),
                     stride=stride, padding=padding, relu=relu, pool=pool,
                     groups=DEPTHWISE, name=name, inputs=_single(input))


def maxpool(size: int = 2, name: Optional[str] = None,
            input: Optional[str] = None) -> LayerSpec:
    return LayerSpec("pool", size=size, name=name, inputs=_single(input))


def avgpool(size: int = 2, name: Optional[str] = None,
            input: Optional[str] = None) -> LayerSpec:
    return LayerSpec("avgpool", size=size, name=name, inputs=_single(input))


def global_pool(name: Optional[str] = None,
                input: Optional[str] = None) -> LayerSpec:
    return LayerSpec("globalpool", name=name, inputs=_single(input))


def flatten(name: Optional[str] = None,
            input: Optional[str] = None) -> LayerSpec:
    return LayerSpec("flatten", name=name, inputs=_single(input))


def dense(features: int, relu: bool = False, name: Optional[str] = None,
          input: Optional[str] = None) -> LayerSpec:
    return LayerSpec("dense", features=features, relu=relu, name=name,
                     inputs=_single(input))


def add(a: str, b: str, relu: bool = False,
        name: Optional[str] = None) -> LayerSpec:
    """Residual merge: elementwise add of two same-shape branches (int8
    path: per-branch requantize onto a shared grid, then a saturating
    int8 add — ref.add_requant_ref)."""
    return LayerSpec("add", relu=relu, name=name, inputs=(a, b))


def concat(*inputs: str, name: Optional[str] = None) -> LayerSpec:
    """Branch merge: concatenate ≥2 branches along the channel axis (int8
    path: each branch requantizes onto the merge node's shared grid)."""
    return LayerSpec("concat", name=name, inputs=tuple(inputs))


@dataclass(frozen=True)
class NetworkPlan:
    """A CNN graph over [H, W, C] inputs.

    ``layers`` is a topologically-ordered node tuple: every node's inputs
    must be earlier nodes (or the network input).  Straight-line plans
    (no ``inputs`` anywhere) behave exactly as before."""
    name: str
    input_shape: Tuple[int, int, int]          # (H, W, C)
    layers: Tuple[LayerSpec, ...]

    # -- graph resolution ---------------------------------------------------

    @functools.cached_property
    def _graph(self) -> Tuple[Tuple[str, ...], Tuple[Tuple[int, ...], ...]]:
        """(node names, resolved input indices), computed and VALIDATED
        once per (frozen) plan instance — every shape/cost/execution walk
        shares this resolution instead of re-deriving it."""
        explicit = {sp.name for sp in self.layers if sp.name}
        names: List[str] = []
        for i, sp in enumerate(self.layers):
            if sp.name:
                if sp.name == INPUT or sp.name in names:
                    raise ValueError(
                        f"duplicate or reserved node name {sp.name!r}")
                names.append(sp.name)
                continue
            nm = f"{sp.kind}{i}"
            while nm == INPUT or nm in explicit:
                nm += "_"
            names.append(nm)
        index = {nm: i for i, nm in enumerate(names)}
        out: List[Tuple[int, ...]] = []
        for i, sp in enumerate(self.layers):
            if sp.inputs:
                idxs = []
                for nm in sp.inputs:
                    if nm == INPUT:
                        idxs.append(-1)
                        continue
                    j = index.get(nm)
                    if j is None:
                        raise ValueError(
                            f"node {names[i]!r}: unknown input {nm!r}")
                    if j >= i:
                        raise ValueError(
                            f"node {names[i]!r}: input {nm!r} does not "
                            "precede it — layers must be topologically "
                            "ordered")
                    idxs.append(j)
                resolved = tuple(idxs)
            else:
                resolved = (i - 1,)
            if sp.kind == "add" and len(resolved) != 2:
                raise ValueError(f"node {names[i]!r}: add takes exactly two "
                                 f"inputs, got {len(resolved)}")
            if sp.kind == "concat" and len(resolved) < 2:
                raise ValueError(f"node {names[i]!r}: concat needs ≥2 inputs")
            if sp.kind not in ("add", "concat") and len(resolved) != 1:
                raise ValueError(f"node {names[i]!r}: {sp.kind} takes one "
                                 f"input, got {len(resolved)}")
            out.append(resolved)
        return tuple(names), tuple(out)

    def node_names(self) -> List[str]:
        """Per-node names (``sp.name`` or ``f"{kind}{i}"``); unique, never
        the reserved input name.  Explicit names own the namespace: an
        auto-generated default that would collide with one (e.g. a user
        node named "conv1" before an unnamed conv at index 1) steps aside
        instead of rejecting the plan."""
        return list(self._graph[0])

    def resolved_inputs(self) -> List[Tuple[int, ...]]:
        """Per-node input indices (−1 = the network input).  Validates the
        graph: referenced nodes must exist and *precede* their consumer
        (the layer tuple is a topological order) and merge arities hold."""
        return list(self._graph[1])

    # -- static shape / cost walks -----------------------------------------

    def activation_shapes(self) -> List[Tuple[int, ...]]:
        """Per-node output shapes (without the batch dim)."""
        names = self.node_names()
        ins = self.resolved_inputs()
        shapes: List[Tuple[int, ...]] = []

        def src(j: int) -> Tuple[int, ...]:
            return self.input_shape if j < 0 else shapes[j]

        for i, sp in enumerate(self.layers):
            s0 = src(ins[i][0])
            if sp.kind in ("conv", "conv_transpose"):
                if len(s0) != 3:
                    raise ValueError(f"node {names[i]!r}: conv after flatten")
                kh, kw = sp.kernel
                k_, _ = conv_geometry(sp, s0[2], names[i])
                if sp.kind == "conv_transpose":
                    h, w = ref.conv_transpose_out_shape(
                        s0[0], s0[1], kh, kw, sp.stride, sp.padding,
                        sp.dilation)
                else:
                    h, w = ref.conv_out_shape(s0[0], s0[1], kh, kw,
                                              sp.stride, sp.padding,
                                              sp.dilation)
                if sp.pool:
                    if h < 2 or w < 2:
                        # same error as plan_tiles / conv2d_ws — the shape
                        # walk must not report a map the kernel rejects
                        raise ValueError(
                            f"node {names[i]!r}: 2×2 pool needs a ≥2×2 "
                            f"conv output, got {h}×{w}")
                    h, w = h // 2, w // 2
                shapes.append((h, w, k_))
            elif sp.kind in ("pool", "avgpool", "globalpool", "flatten"):
                if len(s0) != 3:
                    raise ValueError(f"node {names[i]!r}: {sp.kind} needs "
                                     f"an [H,W,C] input, got shape {s0}")
                h, w, c = s0
                if sp.kind == "globalpool":
                    shapes.append((c,))
                elif sp.kind == "flatten":
                    shapes.append((h * w * c,))
                else:
                    shapes.append(((h - sp.size) // sp.size + 1,
                                   (w - sp.size) // sp.size + 1, c))
            elif sp.kind == "dense":
                if len(s0) != 1:
                    raise ValueError(f"node {names[i]!r}: dense before "
                                     "flatten/globalpool")
                shapes.append((sp.features,))
            elif sp.kind == "add":
                branches = [src(j) for j in ins[i]]
                if len(set(branches)) != 1:
                    raise ValueError(f"node {names[i]!r}: add branches "
                                     f"disagree on shape: {branches}")
                shapes.append(branches[0])
            elif sp.kind == "concat":
                branches = [src(j) for j in ins[i]]
                if any(len(b) != 3 for b in branches) or \
                        len({b[:2] for b in branches}) != 1:
                    raise ValueError(f"node {names[i]!r}: concat branches "
                                     f"must share H×W: {branches}")
                shapes.append((*branches[0][:2],
                               sum(b[2] for b in branches)))
            else:
                raise ValueError(f"unknown layer kind {sp.kind!r}")
        return shapes

    def param_shapes(self) -> List[Optional[dict]]:
        """Per-node {"w": ..., "b": ...} shapes (None for parameter-free
        nodes).  Grouped convs carry the per-group channel slice
        ([KH,KW,C/groups,K] — depthwise weights are [KH,KW,1,C])."""
        ins = self.resolved_inputs()
        acts = self.activation_shapes()
        shapes: List[Optional[dict]] = []
        for i, sp in enumerate(self.layers):
            s0 = self.input_shape if ins[i][0] < 0 else acts[ins[i][0]]
            if sp.kind in ("conv", "conv_transpose"):
                kh, kw = sp.kernel
                k_, g_ = conv_geometry(sp, s0[2])
                shapes.append({"w": (kh, kw, s0[2] // g_, k_),
                               "b": (k_,)})
            elif sp.kind == "dense":
                shapes.append({"w": (s0[0], sp.features),
                               "b": (sp.features,)})
            else:
                shapes.append(None)
        return shapes

    def init_params(self, rng: np.random.Generator) -> List[Optional[dict]]:
        """He-initialized float32 parameters."""
        params: List[Optional[dict]] = []
        for shp in self.param_shapes():
            if shp is None:
                params.append(None)
                continue
            fan_in = int(np.prod(shp["w"][:-1]))
            std = math.sqrt(2.0 / fan_in)
            params.append({
                "w": jnp.asarray(rng.normal(size=shp["w"]) * std,
                                 jnp.float32),
                "b": jnp.asarray(rng.normal(size=shp["b"]) * 0.05,
                                 jnp.float32)})
        return params

    def psum_table(self) -> List[Tuple[str, int]]:
        """Per-node psum counts in the paper's accounting (conv: output
        pixels × kernels × input channels; dense: a 1×1-conv GEMM, in×out;
        pool/flatten/merge: free — the fused epilogue absorbs
        post-processing and the output-BRAM crossbar absorbs residual
        adds/concats).  Parallel branches of a DAG cost their SUM: the
        single layer-at-a-time core serializes them (§4.2).

        Transposed convs are priced on the zero-skipping bound
        (``perfmodel.conv_transpose_psum_count(skip_zeros=True)``: one
        psum per INPUT pixel × tap — the MAC controller skips the
        inserted zeros); the ~stride²× naive count is available from
        perfmodel for what an unmodified IP core would burn."""
        names = self.node_names()
        ins = self.resolved_inputs()
        acts = self.activation_shapes()
        rows: List[Tuple[str, int]] = []
        for i, sp in enumerate(self.layers):
            s0 = self.input_shape if ins[i][0] < 0 else acts[ins[i][0]]
            if sp.kind == "conv":
                kh, kw = sp.kernel
                k_, g_ = conv_geometry(sp, s0[2], names[i])
                rows.append((names[i], perfmodel.psum_count(
                    s0[0], s0[1], s0[2], k_, kh, kw, sp.stride,
                    sp.padding, groups=g_, dilation=sp.dilation)))
            elif sp.kind == "conv_transpose":
                kh, kw = sp.kernel
                k_, g_ = conv_geometry(sp, s0[2], names[i])
                rows.append((names[i], perfmodel.conv_transpose_psum_count(
                    s0[0], s0[1], s0[2], k_, kh, kw, sp.stride,
                    sp.padding, groups=g_, dilation=sp.dilation)))
            elif sp.kind == "dense":
                rows.append((names[i], s0[0] * sp.features))
            else:
                rows.append((names[i], 0))
        return rows

    def tile_plans(self, cin_banks: int = 4, kout_banks: int = 4,
                   in_bytes: int = 1,
                   vmem_budget: Optional[int] = banking.VMEM_LIMIT_BYTES,
                   kernel: str = "auto", calib=None
                   ) -> List[Optional[banking.TilePlan]]:
        """Per-node spatial-tile × channel-bank plans (None for nodes
        without a conv).  int8-datapath sizes by default; the final
        parametric layer (no fused requantize) keeps a 4-byte epilogue
        output, every other conv writes int8.  ``vmem_budget=None``
        disables fitting (whole-map tiles — the seed dataflow).
        ``kernel`` picks the conv variant per layer ("auto" → the
        perfmodel crossover predictor sets ``TilePlan.pipelined`` where
        the explicit DMA pipeline wins; see banking.plan_tiles).
        ``calib`` (a core.calibration.CalibrationTable) prices the
        crossover under measured terms instead of the analytic defaults;
        core/autotune.py searches the full plan space against it.

        Transposed convs are planned on their stride-1 EQUIVALENT conv
        (the zero-inserted map + clipped equivalence pads —
        conv2d_ws_trans.transpose_eq_conv_geometry), which is the
        geometry the kernel lowering actually launches, so VMEM fitting
        and halo math describe the real working set."""
        from repro.kernels.conv2d_ws_trans import transpose_eq_conv_geometry
        last_param = max((i for i, sp in enumerate(self.layers)
                          if sp.kind in PARAM_KINDS), default=-1)
        ins = self.resolved_inputs()
        acts = self.activation_shapes()
        plans: List[Optional[banking.TilePlan]] = []
        for i, sp in enumerate(self.layers):
            if sp.kind not in ("conv", "conv_transpose"):
                plans.append(None)
                continue
            h, w, c = self.input_shape if ins[i][0] < 0 else acts[ins[i][0]]
            kh, kw = sp.kernel
            k_, g_ = conv_geometry(sp, c)
            stride, pad = sp.stride, sp.padding
            if sp.kind == "conv_transpose":
                h, w, pad = transpose_eq_conv_geometry(
                    h, w, kh, kw, sp.stride, sp.padding, sp.dilation)
                stride = 1
            plans.append(banking.plan_tiles(
                h, w, c, k_, kh, kw, stride=stride,
                padding=pad, pool=sp.pool, groups=g_,
                dilation=sp.dilation, in_bytes=in_bytes,
                out_bytes=4 if i == last_param else in_bytes,
                cin_banks=cin_banks, kout_banks=kout_banks,
                vmem_budget=vmem_budget, kernel=kernel, calib=calib))
        return plans

    def conv_geometries(self) -> List[Optional[Tuple[int, int]]]:
        """Per-node resolved (features, groups) for conv nodes (None for
        everything else) — the DEPTHWISE sentinel resolved against each
        node's actual input width, for consumers that need the group
        structure without re-deriving shapes (the int8 compiler, the
        trainer's float shadow)."""
        names = self.node_names()
        ins = self.resolved_inputs()
        acts = self.activation_shapes()
        out: List[Optional[Tuple[int, int]]] = []
        for i, sp in enumerate(self.layers):
            if sp.kind not in ("conv", "conv_transpose"):
                out.append(None)
                continue
            s0 = self.input_shape if ins[i][0] < 0 else acts[ins[i][0]]
            out.append(conv_geometry(sp, s0[2], names[i]))
        return out

    def grouped_layer_count(self) -> int:
        """Number of conv nodes with grouped (groups > 1) contraction —
        the benchmark/report shorthand for "how much of this plan is the
        depthwise workload class"."""
        return sum(1 for g in self.conv_geometries()
                   if g is not None and g[1] > 1)

    def perf_report(self, cfg: perfmodel.IPCoreConfig =
                    perfmodel.IPCoreConfig(),
                    tile_plans: Optional[Sequence] = None,
                    calib=None) -> dict:
        """The §5.2 cycle model summed over the network, including the
        20-core full-board configuration (perfmodel.network_report).
        With ``tile_plans`` (e.g. from :meth:`tile_plans`) the model also
        prices tile revisits and halo re-reads against the DMA interface,
        keeping large-map GOPS honest.  DAG branches serialize on the
        single core, so the sum over nodes is the schedule length.
        ``calib`` applies a measured CalibrationTable to every term;
        omitted, the report is bit-identical to the analytic model."""
        return perfmodel.network_report(self.psum_table(), cfg,
                                        tile_plans=tile_plans, calib=calib)

    def train_report(self, cfg: perfmodel.IPCoreConfig =
                     perfmodel.IPCoreConfig(),
                     tile_plans: Optional[Sequence] = None,
                     calib=None) -> dict:
        """The §5.2 cycle model of one TRAINING step over this plan:
        forward + backward ≈ 3× the forward psums (input-gradient
        transposed conv + weight-gradient correlation each match the
        forward count — perfmodel.train_report), with the f32
        weight-gradient writeback traffic of every parametric node priced
        against the shared DMA interface."""
        wbytes = [None if shp is None else
                  4 * (int(np.prod(shp["w"])) + int(np.prod(shp["b"])))
                  for shp in self.param_shapes()]
        return perfmodel.train_report(self.psum_table(), cfg,
                                      weight_bytes=wbytes,
                                      tile_plans=tile_plans, calib=calib)

    # -- execution ----------------------------------------------------------

    def forward_activations(self, params: Sequence[Optional[dict]],
                            x: jax.Array):
        """Yield (index, spec, layer_params, activation-after-node) through
        the float oracle in graph (tuple) order — the single definition of
        node semantics, shared by ``apply_ref`` and ``quantize_network``.
        Skip/branch inputs are looked up from the per-node activation
        list, so DAG plans walk exactly like straight-line ones.  This
        loop runs EAGERLY (apply_ref / calibration), so each activation is
        released after its last consumer — peak memory stays
        O(live activations), not O(all activations)."""
        ins = self.resolved_inputs()
        last_use = {}
        for i, idxs in enumerate(ins):
            for j in idxs:
                if j >= 0:
                    last_use[j] = i
        # tests/test_network.py asserts the liveness property through this
        # local's name (acts)
        acts: List[Optional[jax.Array]] = []
        for i, (sp, p) in enumerate(zip(self.layers, params)):
            src = [x if j < 0 else acts[j] for j in ins[i]]
            h = src[0]
            if sp.kind == "conv":
                _, g_ = conv_geometry(sp, h.shape[-1])
                h = ref.conv2d_epilogue_ref(
                    h, p["w"], p["b"], stride=sp.stride, padding=sp.padding,
                    relu=sp.relu, pool=sp.pool, groups=g_,
                    dilation=sp.dilation)
            elif sp.kind == "conv_transpose":
                _, g_ = conv_geometry(sp, h.shape[-1])
                h = ref.conv2d_transpose_epilogue_ref(
                    h, p["w"], p["b"], stride=sp.stride, padding=sp.padding,
                    relu=sp.relu, pool=sp.pool, groups=g_,
                    dilation=sp.dilation)
            elif sp.kind == "pool":
                h = ref.maxpool2d_ref(h, sp.size)
            elif sp.kind == "avgpool":
                h = ref.avgpool2d_ref(h, sp.size)
            elif sp.kind == "globalpool":
                h = ref.global_avgpool_ref(h)
            elif sp.kind == "flatten":
                h = h.reshape(h.shape[0], -1)
            elif sp.kind == "dense":
                h = ref.matmul_ref(h, p["w"], p["b"])
                if sp.relu:
                    h = jnp.maximum(h, 0)
            elif sp.kind == "add":
                h = src[0] + src[1]
                if sp.relu:
                    h = jnp.maximum(h, 0)
            elif sp.kind == "concat":
                h = jnp.concatenate(src, axis=-1)
            else:
                raise ValueError(f"unknown layer kind {sp.kind!r}")
            acts.append(h)
            for j in ins[i]:
                if j >= 0 and last_use[j] == i:
                    acts[j] = None               # last consumer passed
            yield i, sp, p, h

    def apply_ref(self, params: Sequence[Optional[dict]], x: jax.Array
                  ) -> jax.Array:
        """Float oracle forward pass (lax.conv; differentiable)."""
        for _, _, _, x in self.forward_activations(params, x):
            pass
        return x


# ---------------------------------------------------------------------------
# int8 network quantization + compilation
# ---------------------------------------------------------------------------


def program_tile_plans(plan: NetworkPlan, core_config) -> List:
    """The per-layer TilePlans a ``make_int8_program`` compile would run
    under ``core_config`` — the single derivation shared by the compiler
    and by benchmark/perf reporting, so reported tiling stats always
    describe the plans that actually executed."""
    return plan.tile_plans(
        cin_banks=core_config.cin_banks,
        kout_banks=core_config.kout_banks, in_bytes=1,
        vmem_budget=(core_config.vmem_budget if core_config.auto_bank
                     else None),
        kernel=getattr(core_config, "kernel", "auto"),
        calib=getattr(core_config, "calib", None))


@dataclass(frozen=True)
class QuantizedNetwork:
    """A NetworkPlan lowered to the 8-bit datapath.

    Per parametric layer i: int8 weights, int32 bias (at scale
    ``s_in·s_w``), and the requantization scale putting the int32
    accumulator on the NEXT layer's int8 grid.  With per-channel (kout)
    weight scales the bias, requant, and dequant entries are [K] vectors —
    the kernel epilogue broadcasts them over the last axis.  The final
    parametric layer keeps ``requant=None`` and the program dequantizes
    its accumulator with ``out_dequant`` (logits want full precision).

    Per merge node i (``add``/``concat``), ``merge_scales[i]`` holds the
    per-branch requant scales (``s_branch / s_out``) aligning each int8
    branch onto the node's shared output grid — the int32-free residual
    add contract (ref.add_requant_ref)."""
    plan: NetworkPlan
    weights: Tuple[Optional[jax.Array], ...]       # int8
    biases: Tuple[Optional[jax.Array], ...]        # int32
    requants: Tuple[Optional[jax.Array], ...]      # f32 scalar or [K]
    in_scale: jax.Array                            # input activation scale
    out_dequant: jax.Array                         # final accumulator scale
    per_channel: bool = False                      # kout-bank weight scales
    merge_scales: Tuple[Optional[Tuple[jax.Array, ...]], ...] = ()


def quantize_network(plan: NetworkPlan, params: Sequence[Optional[dict]],
                     calib_x: jax.Array,
                     per_channel: bool = False) -> QuantizedNetwork:
    """Calibrate activation scales with a float forward pass and lower every
    parametric layer to int8 (symmetric weights).

    ``per_channel=True`` calibrates one weight scale per output channel
    (kout bank) instead of per tensor: conv kernels reduce over
    (KH, KW, C), dense weights over the contraction dim, yielding [K]
    scale vectors that ride the fused requantize epilogue end-to-end —
    the per-channel refinement the paper's per-kernel-set BRAM layout
    makes natural.

    Merge nodes calibrate a SHARED output scale from the float merge
    activation and carry per-branch requant scales (s_branch / s_out):
    each int8 branch re-expresses on the shared grid, so the residual add
    is a pure saturating int8 op — both branches land on the same grid,
    which is the only way the skip add is exact (ref.add_requant_ref is
    the correctness contract)."""
    last_param = max(i for i, sp in enumerate(plan.layers)
                     if sp.kind in PARAM_KINDS)
    ins = plan.resolved_inputs()
    in_scale = act_scale_from_calibration(calib_x)
    node_scale: List[Optional[jax.Array]] = []  # per-node int8 output scale

    def scale_of(j: int) -> jax.Array:
        s = in_scale if j < 0 else node_scale[j]
        if s is None:
            raise ValueError("graph consumes the dequantized float output "
                             "of the final parametric layer")
        return s

    weights: List[Optional[jax.Array]] = []
    biases: List[Optional[jax.Array]] = []
    requants: List[Optional[jax.Array]] = []
    merges: List[Optional[Tuple[jax.Array, ...]]] = []
    out_dequant = jnp.float32(1.0)
    for i, sp, p, x in plan.forward_activations(params, calib_x):
        w_ = b_ = rq = ms = None
        if sp.kind in PARAM_KINDS:
            s_act = scale_of(ins[i][0])
            if per_channel:
                # reduce over everything but the output-channel axis → [K]
                wq = quantize_symmetric(p["w"],
                                        axis=tuple(range(p["w"].ndim - 1)))
                w_scale = wq.scale.reshape(-1)
            else:
                wq = quantize_symmetric(p["w"])
                w_scale = wq.scale
            acc_scale = s_act * w_scale               # int32 psum units
            w_ = wq.values
            b_ = jnp.round(p["b"] / acc_scale).astype(jnp.int32)
            if i == last_param:
                out_dequant = acc_scale
                node_scale.append(None)
            else:
                s_next = act_scale_from_calibration(x)
                rq = requant_scale(s_act, w_scale, s_next)
                node_scale.append(s_next)
        elif sp.kind in ("add", "concat"):
            # shared merge grid: calibrate from the float merge activation,
            # align every branch onto it with a per-branch requant scale
            s_out = act_scale_from_calibration(x)
            ms = tuple(branch_requant_scale(scale_of(j), s_out)
                       for j in ins[i])
            node_scale.append(s_out)
        else:
            # pooling/flatten are monotone/shape-only: the int8 scale
            # carries (avg-pool stays on the same grid — the mean of
            # same-scale values rounds back onto it).  A None scale (the
            # dequantized float tail after the final parametric layer)
            # propagates: these ops run fine on the float output, only
            # parametric/merge consumers need an int8 grid.
            node_scale.append(in_scale if ins[i][0] < 0
                              else node_scale[ins[i][0]])
        weights.append(w_)
        biases.append(b_)
        requants.append(rq)
        merges.append(ms)
    return QuantizedNetwork(plan, tuple(weights), tuple(biases),
                            tuple(requants), in_scale, out_dequant,
                            per_channel=per_channel,
                            merge_scales=tuple(merges))


def int8_forward(qnet: QuantizedNetwork, x: jax.Array, *, backend,
                 tile_plans: Sequence, node_hook=None) -> jax.Array:
    """The int8 forward walk of ``make_int8_program`` as a plain
    function: quantize the input onto the calibrated grid, execute every
    node in topological order through ``backend``, return the final
    activation.  This is the SINGLE definition of int8 node semantics —
    ``make_int8_program`` jits it, and the per-layer profiler
    (obs/profile.py) calls it EAGERLY with a ``node_hook`` so each
    node's output can be block_until_ready'd and wall-clocked
    individually (the layer-at-a-time walk the paper's single IP core
    performs is exactly this loop).

    ``node_hook(i, name, spec, activation)`` is called after each node
    computes; under ``jax.jit`` the hook only fires at trace time, so
    the compiled path must pass None (the compiler enforces nothing —
    profiling a jitted program through the hook is simply meaningless,
    not unsafe)."""
    plan = qnet.plan
    ins = plan.resolved_inputs()
    geoms = plan.conv_geometries()     # resolved (features, groups)
    merges = qnet.merge_scales or (None,) * len(plan.layers)
    names = plan.node_names() if node_hook is not None else None
    qin = jnp.clip(jnp.round(x.astype(jnp.float32) / qnet.in_scale),
                   -128, 127).astype(jnp.int8)
    acts: List[jax.Array] = []
    for i, (sp, w, b, rq, ms, tp) in enumerate(zip(
            plan.layers, qnet.weights, qnet.biases, qnet.requants,
            merges, tile_plans)):
        src = [qin if j < 0 else acts[j] for j in ins[i]]
        h = src[0]
        if sp.kind in ("conv", "conv_transpose"):
            op = (backend.conv_transpose if sp.kind == "conv_transpose"
                  else backend.conv)
            h = op(h, w, b, stride=sp.stride,
                   padding=sp.padding, groups=geoms[i][1],
                   dilation=sp.dilation,
                   relu=sp.relu, pool=sp.pool, out_scale=rq,
                   plan=tp)
            if rq is None:                       # final conv: dequantize
                h = h.astype(jnp.float32) * qnet.out_dequant
        elif sp.kind == "pool":
            # max-pool commutes with the monotone int8 mapping
            h = ref.maxpool2d_ref(h, sp.size)
        elif sp.kind == "avgpool":
            # window mean rounds back onto the same int8 grid
            h = ref.avgpool2d_ref(h, sp.size)
        elif sp.kind == "globalpool":
            h = ref.global_avgpool_ref(h)
        elif sp.kind == "flatten":
            h = h.reshape(h.shape[0], -1)
        elif sp.kind == "dense":
            acc = backend.matmul(h, w, b)        # int32
            if sp.relu:
                acc = jnp.maximum(acc, 0)
            if rq is None:
                h = acc.astype(jnp.float32) * qnet.out_dequant
            else:
                h = ref.requantize_ref(acc, rq)
        elif sp.kind == "add":
            # int32-free residual add: both branches requantize onto
            # the merge node's shared int8 grid, then saturating add
            h = ref.add_requant_ref(src[0], src[1], ms[0], ms[1],
                                    relu=sp.relu)
        elif sp.kind == "concat":
            h = jnp.concatenate(
                [ref.requantize_ref(s, m) for s, m in zip(src, ms)],
                axis=-1)
        acts.append(h)
        if node_hook is not None:
            node_hook(i, names[i], sp, h)
    return acts[-1]


def make_int8_program(qnet: QuantizedNetwork,
                      core_config: ConvCoreConfig = ConvCoreConfig(int8=True),
                      tile_plans: Optional[Sequence] = None):
    """Compile the quantized network into one jitted program
    x_f32 [N,H,W,C] → logits_f32 [N,classes].

    Conv layers run through the backend with the FULL fused epilogue
    (ReLU → pool → requantize in-VMEM) under a per-layer TilePlan — maps
    larger than the VMEM budget stream through halo'd spatial tiles, so
    VGG-small at 64×64+ inputs and ImageNet-scale plans compile; every
    inter-layer tensor is int8.  Dense accumulators requantize inline
    (the GEMM epilogue is a cheap elementwise op XLA fuses into the
    kernel's consumer).

    Nodes compile in the tuple's topological order; skip/branch operands
    are looked up from the per-node output list, and merge nodes execute
    the int8 residual-add / concat contract (per-branch requantize onto
    the shared grid — ref.add_requant_ref).  Because merges consume full
    feature maps AFTER each sharded conv has concatenated its shards,
    kout/spatial-sharded backends see consistent operands by
    construction.

    ``tile_plans`` overrides the per-layer plans (one entry per layer,
    None for non-conv) — pass ``program_tile_plans(qnet.plan,
    core_config)`` to share the exact plans with reporting code."""
    backend = get_backend(core_config.backend)
    plan = qnet.plan
    merges = qnet.merge_scales or (None,) * len(plan.layers)
    if tile_plans is None:
        tile_plans = program_tile_plans(plan, core_config)
    # a short override list would make the compile zip stop early and
    # silently return an intermediate activation as the "logits"
    if len(tile_plans) != len(plan.layers):
        raise ValueError(f"tile_plans needs one entry per node "
                         f"({len(plan.layers)}), got {len(tile_plans)}")
    if len(merges) != len(plan.layers):
        raise ValueError(f"merge_scales needs one entry per node "
                         f"({len(plan.layers)}), got {len(merges)}")

    def program(x: jax.Array) -> jax.Array:
        return int8_forward(qnet, x, backend=backend, tile_plans=tile_plans)

    return jax.jit(program)


# ---------------------------------------------------------------------------
# Reference network zoo
# ---------------------------------------------------------------------------


def lenet(input_shape: Tuple[int, int, int] = (28, 28, 1),
          classes: int = 10) -> NetworkPlan:
    """LeNet-style grayscale classifier exercising the full feature matrix:
    SAME padding, fused conv+pool epilogues, a stride-2 conv, and int8
    dense layers."""
    return NetworkPlan(
        name="lenet", input_shape=input_shape,
        layers=(
            conv(8, kernel=3, padding="SAME", relu=True, pool=True),
            conv(16, kernel=3, padding="SAME", relu=True, pool=True),
            conv(32, kernel=3, stride=2, padding="SAME", relu=True),
            flatten(),
            dense(64, relu=True),
            dense(classes),
        ))


def vgg_small(input_shape: Tuple[int, int, int] = (32, 32, 4),
              classes: int = 10) -> NetworkPlan:
    """VGG-style stacked 3×3 blocks (conv-conv-pool), the shape class the
    paper's full-board replication mode targets.  With 64×64+ inputs the
    per-layer TilePlans stream the early maps through spatial tiles."""
    return NetworkPlan(
        name="vgg_small", input_shape=input_shape,
        layers=(
            conv(16, relu=True), conv(16, relu=True, pool=True),
            conv(32, relu=True), conv(32, relu=True, pool=True),
            conv(64, relu=True, pool=True),
            flatten(),
            dense(128, relu=True),
            dense(classes),
        ))


def vgg_imagenet(input_shape: Tuple[int, int, int] = (224, 224, 4),
                 classes: int = 1000) -> NetworkPlan:
    """ImageNet-scale demo: a VGG-style pyramid over 224×224 inputs whose
    classifier head is a global average pool + one dense layer (no
    flatten + giant GEMM).  Early layers exceed the whole-map VMEM budget
    and compile onto halo'd spatial tiles."""
    return NetworkPlan(
        name="vgg_imagenet", input_shape=input_shape,
        layers=(
            conv(32, relu=True), conv(32, relu=True, pool=True),   # 112
            conv(64, relu=True, pool=True),                        # 56
            conv(128, relu=True, pool=True),                       # 28
            conv(256, relu=True, pool=True),                       # 14
            conv(256, relu=True),
            global_pool(),
            dense(classes),
        ))


def large_map(input_shape: Tuple[int, int, int] = (512, 512, 16),
              classes: int = 4) -> NetworkPlan:
    """Segmentation-scale feature maps: the 512×512×16 first layer's
    whole-map working set exceeds the VMEM budget, so this plan only runs
    through the spatially-tiled kernel — the workload class the seed
    dataflow could not express."""
    return NetworkPlan(
        name="large_map", input_shape=input_shape,
        layers=(
            conv(64, relu=True, pool=True),                        # 256
            conv(32, stride=2, relu=True, pool=True),              # 64
            conv(32, stride=2, relu=True),                         # 32
            avgpool(2),                                            # 16
            global_pool(),
            dense(classes),
        ))


def _basic_block(i: int, src: str, k: int, stride: int,
                 project: Optional[bool] = None) -> List[LayerSpec]:
    """A ResNet basic block: conv-conv plus a skip — identity by default
    for stride 1, a 1×1 stride-s projection otherwise (the He et al.
    option-B shortcut).  A stride-1 block that CHANGES width must pass
    ``project=True`` (the identity skip can't change channel count; the
    shape walk rejects the mismatch otherwise)."""
    if project is None:
        project = stride != 1
    blk = [
        conv(k, stride=stride, relu=True, name=f"b{i}c1", input=src),
        conv(k, relu=False, name=f"b{i}c2"),
    ]
    skip = src
    if project:
        blk.append(conv(k, kernel=1, stride=stride, relu=False,
                        name=f"b{i}p", input=src))
        skip = f"b{i}p"
    blk.append(add(skip, f"b{i}c2", relu=True, name=f"b{i}"))
    return blk


def resnet_small(input_shape: Tuple[int, int, int] = (32, 32, 4),
                 classes: int = 10) -> NetworkPlan:
    """ResNet-style residual classifier: a stem conv, three basic blocks
    (identity skip, then two stride-2 projection-shortcut blocks), global
    average pool, dense head — the skip-connection workload class
    (ResNet/MobileNet families) the straight-line executor could not
    express.  All merges run the int8 shared-grid residual add."""
    layers: List[LayerSpec] = [conv(16, relu=True, name="stem")]
    layers += _basic_block(1, "stem", 16, 1)
    layers += _basic_block(2, "b1", 32, 2)                      # 16×16
    layers += _basic_block(3, "b2", 64, 2)                      # 8×8
    layers += [global_pool(), dense(classes)]
    return NetworkPlan(name="resnet_small", input_shape=input_shape,
                       layers=tuple(layers))


def _ds_block(i: int, k: int, stride: int = 1) -> List[LayerSpec]:
    """A MobileNet-v1 depthwise-separable block: 3×3 depthwise (spatial
    filtering, one kernel per channel) followed by a 1×1 pointwise conv
    (the channel mix) — the factorization that trades the dense conv's
    C·K channel contraction for C + C·K."""
    return [
        depthwise(stride=stride, relu=True, name=f"d{i}"),
        conv(k, kernel=1, relu=True, name=f"p{i}"),
    ]


def mobilenet_small(input_shape: Tuple[int, int, int] = (16, 16, 4),
                    classes: int = 10) -> NetworkPlan:
    """MobileNet-v1-style depthwise-separable classifier: a dense stem,
    then depthwise + pointwise pairs with stride-2 downsampling, global
    average pool, dense head — the edge-CNN workload family the grouped
    conv contract opens up.  Depthwise layers run the degenerate
    one-cin-bank sweep (one kernel set per channel group), so their
    perfmodel rows sit on the shared-DMA floor, not on compute."""
    layers: List[LayerSpec] = [conv(8, relu=True, name="stem")]
    layers += _ds_block(1, 16)
    layers += _ds_block(2, 32, stride=2)                        # 8×8
    layers += _ds_block(3, 32)
    layers += [global_pool(), dense(classes)]
    return NetworkPlan(name="mobilenet_small", input_shape=input_shape,
                       layers=tuple(layers))


def _inverted_residual(i: int, src: str, cin: int, out: int, stride: int,
                       expand: int = 2) -> List[LayerSpec]:
    """A MobileNet-v2 inverted-residual block: 1×1 expand (×``expand``) →
    3×3 depthwise → linear 1×1 project, with an identity skip add (the
    PR-3 DAG merge) when the block keeps shape.  The projection conv is
    deliberately relu=False — v2's linear bottleneck."""
    blk = [
        conv(cin * expand, kernel=1, relu=True, name=f"m{i}e", input=src),
        depthwise(stride=stride, relu=True, name=f"m{i}d"),
        conv(out, kernel=1, relu=False, name=f"m{i}p"),
    ]
    if stride == 1 and cin == out:
        blk.append(add(src, f"m{i}p", name=f"m{i}"))
    return blk


def mobilenet_v2ish(input_shape: Tuple[int, int, int] = (16, 16, 4),
                    classes: int = 10) -> NetworkPlan:
    """MobileNet-v2-style inverted-residual classifier: expand → depthwise
    → linear-project blocks whose identity skips reuse the residual-graph
    int8 merge (shared-grid saturating add), stacking grouped convs onto
    the DAG story — the second half of the edge workload family."""
    layers: List[LayerSpec] = [conv(8, relu=True, name="stem")]
    layers += _inverted_residual(1, "stem", 8, 8, 1)            # skip add
    layers += _inverted_residual(2, "m1", 8, 16, 2)             # 8×8
    layers += _inverted_residual(3, "m2p", 16, 16, 1)           # skip add
    layers += [global_pool(), dense(classes)]
    return NetworkPlan(name="mobilenet_v2ish", input_shape=input_shape,
                       layers=tuple(layers))


def resnet_bottleneck(input_shape: Tuple[int, int, int] = (32, 32, 8),
                      classes: int = 10) -> NetworkPlan:
    """Bottleneck-residual variant (the ResNet-50 block family): 1×1
    reduce → 3×3 → 1×1 expand with projection shortcuts, exercising 1×1
    convs and width changes through the merge-node int8 story."""
    def bottleneck(i: int, src: str, mid: int, out: int,
                   stride: int) -> List[LayerSpec]:
        return [
            conv(mid, kernel=1, stride=stride, relu=True, name=f"b{i}r",
                 input=src),
            conv(mid, relu=True, name=f"b{i}c"),
            conv(out, kernel=1, relu=False, name=f"b{i}e"),
            conv(out, kernel=1, stride=stride, relu=False, name=f"b{i}p",
                 input=src),
            add(f"b{i}p", f"b{i}e", relu=True, name=f"b{i}"),
        ]

    layers: List[LayerSpec] = [conv(16, relu=True, name="stem")]
    layers += bottleneck(1, "stem", 8, 32, 1)
    layers += bottleneck(2, "b1", 16, 64, 2)                    # 16×16
    layers += [global_pool(), dense(classes)]
    return NetworkPlan(name="resnet_bottleneck", input_shape=input_shape,
                       layers=tuple(layers))


def unet_small(input_shape: Tuple[int, int, int] = (16, 16, 4),
               classes: int = 3) -> NetworkPlan:
    """U-Net-style encoder–decoder segmenter: two stride-2 downsampling
    stages, a bottleneck, then two 2×2/stride-2 ``conv_transpose``
    upsampling stages each concat-merged with its same-resolution encoder
    skip (the U-Net long skip, riding the shared-grid int8 concat), and a
    1×1 per-pixel classifier head — the dense-prediction workload class
    ROADMAP item 5(b) names.  The output is a full-resolution
    [H, W, classes] logit map, not a vector."""
    return NetworkPlan(
        name="unet_small", input_shape=input_shape,
        layers=(
            conv(8, relu=True, name="enc1"),                       # 16×16
            conv(16, stride=2, relu=True, name="down1"),           # 8×8
            conv(16, relu=True, name="enc2"),
            conv(32, stride=2, relu=True, name="down2"),           # 4×4
            conv(32, relu=True, name="bott"),
            conv_transpose(16, kernel=2, stride=2, relu=True,
                           name="up1"),                            # 8×8
            concat("up1", "enc2", name="cat1"),
            conv(16, relu=True, name="dec1"),
            conv_transpose(8, kernel=2, stride=2, relu=True,
                           name="up2"),                            # 16×16
            concat("up2", "enc1", name="cat2"),
            conv(8, relu=True, name="dec2"),
            conv(classes, kernel=1, relu=False, name="head"),
        ))


def dilated_context(input_shape: Tuple[int, int, int] = (16, 16, 4),
                    classes: int = 3) -> NetworkPlan:
    """Dilated-context segmenter (the DeepLab/context-module idiom): a
    stem plus SAME-padded 3×3 convs at dilation 1 → 2 → 4 keep the map at
    full resolution while the receptive field grows exponentially
    (15×15 after the d=4 layer) — dense prediction WITHOUT any
    down/upsampling, the workload dilation exists for.  A 1×1 head emits
    the per-pixel logit map."""
    return NetworkPlan(
        name="dilated_context", input_shape=input_shape,
        layers=(
            conv(8, relu=True, name="stem"),
            conv(8, relu=True, dilation=2, name="ctx2"),
            conv(16, relu=True, dilation=4, name="ctx4"),
            conv(16, relu=True, name="fuse"),
            conv(classes, kernel=1, relu=False, name="head"),
        ))
