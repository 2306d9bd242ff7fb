"""Useful operations and least bytes of each layer, from published shapes.

These are the work the model needs, not what a kernel happens to execute:
channels a kernel zero-extends do not count, a grouped or depthwise
convolution counts only the input channels of each output's group, and a
transposed convolution counts each input pixel's taps once (the zeros an
implementation inserts do not count).  Bytes are the least any
implementation must move: the layer's int8 input map, its int8 weights and
int32 biases, and its output map (int8, or float32 for the last parametric
layer, whose output is dequantized).  So a share of a roofline built on
them reads the same work whatever later change implements a layer, and
cannot pass 100%.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

# JSON ``kind`` is the name of the ``repro.core.network`` constructor
PARAM_KINDS = ("conv", "depthwise", "conv_transpose", "dense")
INPUT = "input"     # the name by which a node reads the network's input


@dataclasses.dataclass(frozen=True)
class LayerCount:
    name: str
    kind: str
    macs: int           # per image
    in_bytes: int       # per image
    out_bytes: int      # per image
    weight_bytes: int   # per batch (weights are read once)

    @property
    def ops(self) -> int:
        return 2 * self.macs

    def least_time(self, batch: int, peak_ops: float,
                   peak_bytes: float) -> Tuple[float, str]:
        """(seconds, bound): the larger of ops over peak op/s and bytes
        over peak bytes/s for one batch, and which of the two it is."""
        t_ops = batch * self.ops / peak_ops
        t_bytes = (batch * (self.in_bytes + self.out_bytes)
                   + self.weight_bytes) / peak_bytes
        return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes,
                                                             "memory")


def _window_out(n: int, extent: int, stride: int, padding: str) -> int:
    """Outputs along one axis of ``n`` for a window of ``extent``."""
    if padding == "SAME":
        return -(-n // stride)
    if padding == "VALID":
        return (n - extent) // stride + 1
    raise ValueError(f"padding {padding!r}: only SAME or VALID")


def _conv(sp: dict, src: tuple, out_itemsize: int):
    """(output shape, LayerCount) of a conv, depthwise or VALID transposed
    conv.  ``groups`` -1 is depthwise, as ``network.conv_geometry`` reads
    it: one group per input channel, ``features`` defaulting to them."""
    kind, name = sp["kind"], sp["name"]
    h, w, c = src
    transpose = kind == "conv_transpose"
    k = sp.get("kernel", 2 if transpose else 3)
    stride = sp.get("stride", 2 if transpose else 1)
    padding = sp.get("padding", "VALID" if transpose else "SAME")
    extent = (k - 1) * sp.get("dilation", 1) + 1
    depthwise = kind == "depthwise" or sp.get("groups", 1) == -1
    groups = c if depthwise else sp.get("groups", 1)
    f = sp.get("features") or (c if depthwise else 0)
    if f <= 0 or groups < 1 or c % groups or f % groups:
        raise ValueError(f"{name}: {f} features in {groups} groups over "
                         f"{c} channels")
    taps = k * k * (c // groups) * f
    if transpose:
        if padding != "VALID":
            raise ValueError(f"{name}: only VALID up-convolutions")
        oh, ow = (h - 1) * stride + extent, (w - 1) * stride + extent
        macs = h * w * taps
    else:
        oh = _window_out(h, extent, stride, padding)
        ow = _window_out(w, extent, stride, padding)
        macs = oh * ow * taps
    if sp.get("pool"):
        oh, ow = oh // 2, ow // 2
    return (oh, ow, f), LayerCount(name, kind, macs, h * w * c,
                                   oh * ow * f * out_itemsize, taps + 4 * f)


def walk(cfg: dict) -> List[Tuple[str, tuple, Optional[LayerCount]]]:
    """Each node of ``cfg["layers"]`` in order: its name, its output shape
    (without the batch) and its LayerCount, None for a node without
    weights.  Pools and merges count nothing: a fused residual add reads a
    skip map these bytes leave out, so a share built on them reads low."""
    layers = cfg["layers"]
    last_param = max(i for i, sp in enumerate(layers)
                     if sp["kind"] in PARAM_KINDS)
    shapes: Dict[str, tuple] = {INPUT: tuple(cfg["input_shape"])}
    prev = shapes[INPUT]
    out: List[Tuple[str, tuple, Optional[LayerCount]]] = []
    for i, sp in enumerate(layers):
        kind, name = sp["kind"], sp["name"]
        src = shapes[sp["inputs"][0]] if sp.get("inputs") else prev
        out_itemsize = 4 if i == last_param else 1
        count = None
        if kind in ("conv", "depthwise", "conv_transpose"):
            shape, count = _conv(sp, src, out_itemsize)
        elif kind == "dense":
            (d,) = src
            f = sp["features"]
            shape = (f,)
            count = LayerCount(name, kind, d * f, d, f * out_itemsize,
                               d * f + 4 * f)
        elif kind in ("maxpool", "avgpool"):
            h, w, c = src
            size = sp.get("size", 2)
            stride = sp.get("stride", size)
            padding = sp.get("padding", "VALID")
            shape = (_window_out(h, size, stride, padding),
                     _window_out(w, size, stride, padding), c)
        elif kind == "global_pool":
            _, _, c = src
            shape = (c,)
        elif kind == "flatten":
            shape = (src[0] * src[1] * src[2],)
        elif kind == "add":
            parts = [shapes[n] for n in sp["inputs"]]
            if len(parts) != 2 or parts[0] != parts[1]:
                raise ValueError(f"{name}: add takes two maps of one "
                                 f"shape, got {parts}")
            shape = parts[0]
        elif kind == "concat":
            parts = [shapes[n] for n in sp["inputs"]]
            shape = (*parts[0][:2], sum(p[2] for p in parts))
        else:
            raise ValueError(f"{name}: no count for layer kind {kind!r}")
        shapes[name] = prev = shape
        out.append((name, shape, count))
    return out


def layer_counts(cfg: dict) -> List[LayerCount]:
    """One LayerCount per parametric layer of the configuration ``cfg``."""
    return [count for _, _, count in walk(cfg) if count is not None]


def useful_ops_per_image(cfg: dict) -> int:
    return sum(lc.ops for lc in layer_counts(cfg))
