"""Plain reference of U-Net (arXiv:1505.04597, Fig. 1), SAME-padded.

Contracting path: per level two 3x3 convolutions with ReLU, the result
kept as the skip, then a 2x2/2 max pool; widths 64, 128, 256, 512 and a
1024-wide bottom level.  Expansive path: per level a 2x2/2 up-convolution
that halves the channels, concatenation [skip, up], then two 3x3
convolutions with ReLU.  A 1x1 convolution gives the class logits per
pixel.  ``unet.json`` lists the departures from the paper (SAME padding in
place of VALID convolutions and cropping, and where ReLUs go).  Widths are
read from the parameters, so the same code runs small test copies.
"""

from __future__ import annotations

WIDTHS = (64, 128, 256, 512)
BOTTOM = 1024


def param_shapes(input_shape, widths=WIDTHS, bottom=BOTTOM,
                 classes: int = 2):
    """[(w_shape, b_shape), ...] in layer order, at the published widths
    unless others are given."""
    c = input_shape[2]
    shapes = []
    for k in (*widths, bottom):
        shapes += [((3, 3, c, k), (k,)), ((3, 3, k, k), (k,))]
        c = k
    for k in reversed(widths):
        shapes += [((2, 2, c, k), (k,)), ((3, 3, 2 * k, k), (k,)),
                   ((3, 3, k, k), (k,))]
        c = k
    shapes.append(((1, 1, c, classes), (classes,)))
    return shapes


def forward(params, x, act=None):
    """Logit maps [N, H, W, classes] of images [N, H, W, C]; ``params`` and
    ``act`` as in ``vgg16.forward``."""
    import jax.numpy as jnp

    from perfbench.harness import plain
    act = act or (lambda i, h: h)
    it = iter(range(len(params)))

    def conv(h, op=plain.conv_same, relu=True):
        i = next(it)
        w, b = params[i]
        h = op(act(i, h), w, b)
        return plain.relu(h) if relu else h

    skips, h = [], x
    for _ in WIDTHS:
        h = conv(conv(h))
        skips.append(h)
        h = plain.maxpool2(h)
    h = conv(conv(h))
    for skip in reversed(skips):
        h = conv(h, op=plain.up_conv, relu=False)
        h = jnp.concatenate([skip, h], axis=-1)
        h = conv(conv(h))
    return conv(h, relu=False)
