"""Logical-axis sharding rules → PartitionSpecs / NamedShardings.

Axis semantics (see layers/common.py for the logical-name glossary):

* Parameters: TP axes ("heads", "mlp", "vocab", "experts", "rnn", "qkv")
  map to the "model" mesh axis; with FSDP on, the "embed" axis is
  additionally sharded over the FSDP axes (ZeRO-style — parameters,
  gradients and optimizer state all follow the same spec, so XLA emits
  reduce-scatter + all-gather instead of all-reduce in the backward pass).
* Activations: "batch" maps to the DP axes (("pod","data") on the
  multi-pod mesh); "cache_seq" maps to "model" in *decode* mode only —
  a sequence-sharded KV cache makes the per-step cache read perfectly
  parallel and keeps softmax collectives at [B, heads]-scalar size
  (DESIGN.md §Distribution).

Conflicts (a tensor whose logical axes map to the same mesh axis twice,
e.g. MoE weights [experts, embed, mlp] with experts→model and mlp→model)
are resolved first-come-first-served along dimensions, matching MaxText.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.layers.common import ParamSpec, is_spec, resolve_pspec, spec_map


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def param_rules(mesh: Mesh, fsdp: bool) -> Dict[str, Any]:
    rules = {
        "heads": "model",
        "qkv": "model",
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "rnn": "model",
        "kv_heads": None,
        "head_dim": None,
        "stack": None,
        "embed": _dp_axes(mesh) if fsdp else None,
    }
    return rules


def act_rules(mesh: Mesh, mode: str, seq_shard: bool = False) -> Dict[str, Any]:
    """mode: train | prefill | decode.

    seq_shard: Megatron-SP-style residual-stream sequence sharding ("seq_r"
    is the residual sequence axis, used only on between-block constraints).
    Forward wire is AG+RS ≈ the AR it replaces, but every backward dgrad
    psum becomes the *transpose of an all-gather* — a reduce-scatter at half
    the wire (§Perf iteration A2).  Only valid when no block mixes along
    time sequentially (recurrent archs keep seq local)."""
    rules = {
        "batch": _dp_axes(mesh),
        "seq": None,
        "seq_r": "model" if seq_shard else None,
        "embed": None,
        "heads": "model",
        "kv_heads": None,
        "qkv": "model",
        "head_dim": None,
        "mlp": "model",
        "vocab": "model",
        "experts": "model",
        "rnn": "model",
        "cache_seq": "model" if mode == "decode" else None,
    }
    return rules


def axes_to_pspec(axes: Tuple[Optional[str], ...], rules: Dict[str, Any]) -> P:
    """Map logical axes to a PartitionSpec, dropping mesh-axis reuse."""
    return resolve_pspec(axes, rules)


def _fits(shape, spec: P, mesh: Mesh) -> P:
    """Drop sharding on dims not divisible by their mesh-axis size."""
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(entry if dim % size == 0 else None)
    return P(*out)


# when a logical axis cannot take its mesh axis (divisibility), try moving
# the mesh axis to one of these sibling dims instead (yi-34b: 56 heads don't
# divide model=16, so q/o projections shard head_dim — without this they
# would silently replicate, +12 GB/device)
_FALLBACKS = {"heads": ("head_dim",)}


def spec_shardings(spec_tree, mesh: Mesh, rules: Dict[str, Any]):
    """ParamSpec pytree → NamedSharding pytree (divisibility-safe, with
    per-axis fallbacks)."""
    def f(s: ParamSpec):
        raw = axes_to_pspec(s.axes, rules)
        pspec = _fits(s.shape, raw, mesh)
        # re-place dropped mesh axes on fallback dims
        entries = list(tuple(pspec) + (None,) * (len(s.shape) - len(pspec)))
        raw_entries = tuple(raw) + (None,) * (len(s.shape) - len(raw))
        for i, (want, got) in enumerate(zip(raw_entries, entries)):
            if want is None or got is not None:
                continue
            name = s.axes[i]
            for fb in _FALLBACKS.get(name, ()):
                for j, ax_name in enumerate(s.axes):
                    if ax_name != fb or entries[j] is not None:
                        continue
                    size = mesh.shape[want] if isinstance(want, str) else 0
                    if size and s.shape[j] % size == 0:
                        entries[j] = want
                        break
                else:
                    continue
                break
        return NamedSharding(mesh, P(*entries))
    return spec_map(f, spec_tree)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int = 2):
    """Inputs: [B, ...] sharded over the DP axes."""
    return NamedSharding(mesh, P(_dp_axes(mesh), *([None] * (ndim - 1))))


def input_shardings(input_tree, mesh: Mesh):
    """ShapeDtypeStruct tree → batch-sharded NamedShardings (dim 0 = batch),
    dropping the constraint when the batch dim does not divide."""
    def f(s):
        dp = _dp_axes(mesh)
        size = 1
        for a in dp:
            size *= mesh.shape[a]
        if s.shape and s.shape[0] % size == 0:
            return NamedSharding(mesh, P(dp, *([None] * (len(s.shape) - 1))))
        return NamedSharding(mesh, P())
    return jax.tree.map(f, input_tree)


@dataclass
class ShardingPlan:
    """Everything a step builder needs to place one (arch × shape) cell."""
    mesh: Mesh
    fsdp: bool
    mode: str                       # train | prefill | decode
    seq_shard: bool = False         # residual-stream SP (see act_rules)

    @property
    def params(self) -> Dict[str, Any]:
        return param_rules(self.mesh, self.fsdp)

    @property
    def acts(self) -> Dict[str, Any]:
        return act_rules(self.mesh, self.mode, self.seq_shard)

    def param_shardings(self, spec_tree):
        return spec_shardings(spec_tree, self.mesh, self.params)

    def cache_shardings(self, cache_spec_tree):
        # caches are activations: batch + cache_seq rules apply
        return spec_shardings(cache_spec_tree, self.mesh, self.acts)

    def input_shardings(self, input_tree):
        return input_shardings(input_tree, self.mesh)
