"""Whether the served answers are correct: the comparison with the plain
reference, and the lower-precision control that it has to fail.

The answers the run kept (each pool image's first, and a seeded share of
the rest) are compared (``errors``) with the configuration's float32
forward pass at ``Precision.HIGHEST`` on the same weights and image, by L2
over the logits of an image or over its whole logit map.  Answers
bit-identical to one already compared for the same image are not compared
again.
"""

from __future__ import annotations

from typing import Dict, List, Mapping

import numpy as np

BLOCK = 4          # images per reference call


def _blocked(fn, images: np.ndarray, block: int = BLOCK) -> np.ndarray:
    """``fn`` over ``images`` in blocks of ``block``, the last one padded
    so that one compiled shape serves every block."""
    import jax.numpy as jnp
    outs = []
    for lo in range(0, images.shape[0], block):
        chunk = images[lo:lo + block]
        n = chunk.shape[0]
        if n < block:
            chunk = np.concatenate(
                [chunk, np.zeros((block - n, *chunk.shape[1:]), chunk.dtype)])
        outs.append(np.asarray(fn(jnp.asarray(chunk)))[:n])
    return np.concatenate(outs)


def reference_answers(ref_mod, weights, images: np.ndarray) -> np.ndarray:
    import jax
    fwd = jax.jit(ref_mod.forward)
    return _blocked(lambda x: fwd(weights, x), images)


def lower_precision_answers(ref_mod, weights, calib: np.ndarray,
                            images: np.ndarray, bits: int) -> np.ndarray:
    """The reference put in the program's place at ``bits`` bits: weights
    on per-tensor symmetric grids, the input of every parametric layer on
    a grid calibrated, as the program calibrates, on ``calib``."""
    import jax
    import jax.numpy as jnp

    from perfbench.harness import plain

    def scales(weights, x):
        found: List = []

        def record(i, h):
            found.append(plain.symmetric_scale(h, bits))
            return h
        ref_mod.forward(weights, x, act=record)
        return found

    act_scales = jax.jit(scales)(weights, jnp.asarray(calib))
    wq = [(plain.fake_quant(w, plain.symmetric_scale(w, bits), bits), b)
          for w, b in weights]

    def forward(wq, s, x):
        return ref_mod.forward(
            wq, x, act=lambda i, h: plain.fake_quant(h, s[i], bits))

    fwd = jax.jit(forward)
    return _blocked(lambda x: fwd(wq, act_scales, x), images)


def errors(answers: Mapping[int, np.ndarray], pool_idx: np.ndarray,
           ref: Mapping[int, np.ndarray]) -> Dict[str, float]:
    """The numbers compared, over the answers ``answers`` (request -> answer)
    of requests for pool images ``pool_idx[request]``:

    * ``max_rel_err``: the largest ||a - r|| / ||r|| of an answer ``a``
      against the reference ``r`` of its image;
    * ``max_centred_err``: the same with each side's mean over the pool
      taken out, ||(a - a_mean) - (r - r_mean)|| / ||r - r_mean||, where
      ``a_mean`` is the mean of the first answers for each pool image and
      ``r_mean`` the mean of their references.  Where a network answers
      every image much alike, as VGG-16 does with random weights, only
      what sets one image apart from the others is left to compare, so an
      answer given to the wrong request reads about sqrt(2) and one answer
      given to every request reads 1.
    """
    first: Dict[int, np.ndarray] = {}
    for i in sorted(answers):
        first.setdefault(int(pool_idx[i]), answers[i])
    if len(first) < 2:
        raise ValueError("the centred error needs answers for two images")
    a_mean = np.mean([np.asarray(a, np.float64) for a in first.values()], 0)
    r_mean = np.mean([np.asarray(ref[k], np.float64) for k in first], 0)
    worst = {"max_rel_err": 0.0, "max_centred_err": 0.0}
    seen: Dict[int, List[np.ndarray]] = {}
    for i, a in answers.items():
        k = int(pool_idx[i])
        done = seen.setdefault(k, [])
        if any(np.array_equal(a, b) for b in done):
            continue
        done.append(a)
        a = np.asarray(a, np.float64)
        r = np.asarray(ref[k], np.float64)
        rc = r - r_mean
        for name, gap, scale in (
                ("max_rel_err", a - r, r),
                ("max_centred_err", (a - a_mean) - rc, rc)):
            worst[name] = max(worst[name], float(
                np.linalg.norm(gap) / np.linalg.norm(scale)))
    return worst


def swapped(answers: Mapping[int, np.ndarray],
            pool_idx: np.ndarray) -> Dict[int, np.ndarray]:
    """A fault: the first answers for pool images taken in pairs, each
    given to the other image's request."""
    first: Dict[int, int] = {}
    for i in sorted(answers):
        first.setdefault(int(pool_idx[i]), i)
    reqs = [first[k] for k in sorted(first)]
    out = {}
    for a, b in zip(reqs[0::2], reqs[1::2]):
        out[a], out[b] = answers[b], answers[a]
    return out


def lines(checks: dict) -> List[str]:
    return [f"check {name}: {c['value']!r} (limit {c['limit']!r})"
            for name, c in checks.items()]
