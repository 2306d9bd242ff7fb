"""Weight-stationary blocked GEMM — the paper's dataflow generalized to the
matmuls that dominate transformers (a 1×1 convolution *is* a GEMM; this is
the TPU-native statement of the IP-core architecture — DESIGN.md §4).

Same four mechanisms as conv2d_ws:
* contraction (K) banking with output-block revisiting & accumulation
  (channel banks → PSUM accumulation into the output BRAM): grid =
  (N-blocks, M-blocks, K-blocks), k innermost, so an output block stays
  VMEM-resident across its whole contraction sweep and is written back
  once — on the chip an output block is flushed when its index changes,
  so the contraction must be the innermost axis;
* bias preload at the first contraction bank (M5);
* Pallas double-buffered block DMA = the load/compute pipeline (M4).

Blocks are multiples of 128 or the full extent (the MXU/lane tiling
Mosaic accepts); ``_pick`` legalizes the requested sizes.

int8×int8→int32 supported (the 8-bit datapath).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import LANES


def _mm_kernel(x_ref, w_ref, b_ref, o_ref, *, acc_dtype):
    ko = pl.program_id(2)

    @pl.when(ko == 0)
    def _init():
        o_ref[...] = jnp.broadcast_to(
            b_ref[...].astype(acc_dtype), o_ref.shape)

    o_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                          preferred_element_type=acc_dtype)


def _pick(total: int, target: int) -> int:
    """Largest divisor of ``total`` that is ≤ target and a multiple of 128
    lanes; the full extent when there is none (e.g. a 1000-class head)."""
    t = min(target, total) // LANES * LANES
    while t and total % t:
        t -= LANES
    return t or total


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def matmul_ws(x, w, bias=None, *, bm: int = 256, bk: int = 512, bn: int = 256,
              interpret: bool = False):
    """x: [M,K] @ w: [K,N] (+bias [N]) → [M,N] (f32, or int32 for int8 in).

    Default blocks: bm×bk×bn = 256×512×256 → VMEM working set
    (x 256×512 + w 512×256 + out 256×256) ≈ 2.5 MiB in f32 with double
    buffering — well under the 16 MiB scoped VMEM, MXU-aligned (×128).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    bm, bk, bn = _pick(m, bm), _pick(k, bk), _pick(n, bn)

    int_path = x.dtype == jnp.int8
    acc_dtype = jnp.int32 if int_path else jnp.float32
    if bias is None:
        bias = jnp.zeros((n,), acc_dtype)
    bias = bias.astype(acc_dtype)

    out = pl.pallas_call(
        functools.partial(_mm_kernel, acc_dtype=acc_dtype),
        grid=(n // bn, m // bm, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda no, mo, ko: (mo, ko)),
            pl.BlockSpec((bk, bn), lambda no, mo, ko: (ko, no)),
            pl.BlockSpec((1, bn), lambda no, mo, ko: (0, no)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda no, mo, ko: (mo, no)),
        out_shape=jax.ShapeDtypeStruct((m, n), acc_dtype),
        interpret=interpret,
    )(x, w, bias.reshape(1, n))
    return out
