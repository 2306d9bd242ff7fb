"""Useful operations and least bytes of each layer, from published shapes.

These are the work the model needs, not what a kernel happens to execute:
channels a kernel zero-extends do not count, and a transposed convolution
counts each input pixel's taps once (the zeros an implementation inserts
do not count).  Bytes are the least any implementation must move: the
layer's int8 input map, its int8 weights and int32 biases, and its output
map (int8, or float32 for the last parametric layer, whose output is
dequantized).  So a share of a roofline built on them reads the same work
whatever later change implements a layer, and cannot pass 100%.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

PARAM_KINDS = ("conv", "conv_transpose", "dense")


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


def _conv_out(h: int, w: int, k: int, stride: int, padding: str):
    if padding == "SAME":
        return -(-h // stride), -(-w // stride)
    return (h - k) // stride + 1, (w - k) // stride + 1


def layer_counts(cfg: dict) -> List[LayerCount]:
    """One LayerCount per parametric layer of the configuration ``cfg``."""
    layers = cfg["layers"]
    last_param = max(i for i, sp in enumerate(layers)
                     if sp["kind"] in PARAM_KINDS)
    shapes: Dict[str, tuple] = {}
    prev = tuple(cfg["input_shape"])
    out: List[LayerCount] = []
    for i, sp in enumerate(layers):
        kind, name = sp["kind"], sp["name"]
        src = shapes[sp["inputs"][0]] if sp.get("inputs") else prev
        out_itemsize = 4 if i == last_param else 1
        if kind in ("conv", "conv_transpose"):
            h, w, c = src
            k = sp.get("kernel", 3 if kind == "conv" else 2)
            stride = sp.get("stride", 1 if kind == "conv" else 2)
            f = sp["features"]
            if kind == "conv":
                oh, ow = _conv_out(h, w, k, stride,
                                   sp.get("padding", "SAME"))
                macs = oh * ow * k * k * c * f
            else:
                if sp.get("padding", "VALID") != "VALID":
                    raise ValueError(f"{name}: only VALID up-convolutions")
                oh, ow = (h - 1) * stride + k, (w - 1) * stride + k
                macs = h * w * k * k * c * f
            if sp.get("pool"):
                oh, ow = oh // 2, ow // 2
            shape = (oh, ow, f)
            out.append(LayerCount(name, kind, macs, h * w * c,
                                  oh * ow * f * out_itemsize,
                                  k * k * c * f + 4 * f))
        elif kind == "dense":
            (d,) = src
            f = sp["features"]
            shape = (f,)
            out.append(LayerCount(name, kind, d * f, d, f * out_itemsize,
                                  d * f + 4 * f))
        elif kind == "maxpool":
            h, w, c = src
            size = sp.get("size", 2)
            shape = (h // size, w // size, c)
        elif kind == "flatten":
            shape = (src[0] * src[1] * src[2],)
        elif kind == "concat":
            parts = [shapes[n] for n in sp["inputs"]]
            shape = (*parts[0][:2], sum(p[2] for p in parts))
        else:
            raise ValueError(f"{name}: no count for layer kind {kind!r}")
        shapes[name] = prev = shape
    return out


def useful_ops_per_image(cfg: dict) -> int:
    return sum(lc.ops for lc in layer_counts(cfg))
