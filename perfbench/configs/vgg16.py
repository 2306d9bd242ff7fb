"""Plain reference of VGG-16 (arXiv:1409.1556, Table 1 column D).

Five stages of SAME 3x3 convolutions with ReLU (2, 2, 3, 3 and 3 of them
at widths 64, 128, 256, 512, 512), each stage closed by a 2x2/2 max pool;
then flatten and three fully connected layers, 4096-ReLU, 4096-ReLU and
the class logits.  Dropout and softmax are left out (``vgg16.json`` lists
the departures).  Widths are read from the parameters, so the same code
runs the published sizes and small test copies.
"""

from __future__ import annotations

STAGES = (2, 2, 3, 3, 3)
WIDTHS = (64, 128, 256, 512, 512)
FC = (4096, 4096)


def param_shapes(input_shape, widths=WIDTHS, fc=FC, classes: int = 1000):
    """[(w_shape, b_shape), ...] in layer order, at the published widths
    unless others are given."""
    h, w, c = input_shape
    shapes = []
    for n, k in zip(STAGES, widths):
        for _ in range(n):
            shapes.append(((3, 3, c, k), (k,)))
            c = k
        h, w = h // 2, w // 2
    d = h * w * c
    for f in (*fc, classes):
        shapes.append(((d, f), (f,)))
        d = f
    return shapes


def forward(params, x, act=None):
    """Logits [N, classes] of images [N, H, W, C].  ``params`` is the list
    of (w, b) in layer order; ``act(i, h)``, when given, replaces the input
    ``h`` of parametric layer ``i`` (the lower-precision control uses it)."""
    from perfbench.harness import plain
    act = act or (lambda i, h: h)
    i, h = 0, x
    for n in STAGES:
        for _ in range(n):
            w, b = params[i]
            h = plain.relu(plain.conv_same(act(i, h), w, b))
            i += 1
        h = plain.maxpool2(h)
    h = h.reshape(h.shape[0], -1)
    for last in (False, False, True):
        w, b = params[i]
        h = plain.dense(act(i, h), w, b)
        if not last:
            h = plain.relu(h)
        i += 1
    return h
