"""Plain reference of ResNet-50 v1.5 (arXiv:1512.03385, Table 1, 50-layer).

A 7x7/2 stem conv to 64 channels with ReLU and a 3x3/2 SAME max pool;
then four stages of bottleneck blocks (3, 4, 6 and 3 of them at widths
64, 128, 256 and 512): a 1x1 reduce with ReLU, a 3x3 conv with ReLU, a
1x1 expand to four times the width without ReLU, and the sum with the
block's input, then ReLU.  Where a block changes the width (each stage's
first block) the input reaches the sum through a 1x1 projection without
ReLU, and from the second stage on that block's 3x3 conv and projection
take the stage's stride 2 (v1.5: the stride sits on the 3x3, not on the
first 1x1).  A global average pool and the fully connected layer give the
logits.  Batch norm is folded into the conv biases and the softmax is
left out (``resnet50.json`` lists the departures).  Widths and the blocks'
layout are read from the parameters, so the same code runs the published
sizes and small test copies.
"""

from __future__ import annotations

STEM = 64
WIDTHS = (64, 128, 256, 512)
BLOCKS = (3, 4, 6, 3)
EXPANSION = 4


def param_shapes(input_shape, stem: int = STEM, widths=WIDTHS,
                 blocks=BLOCKS, classes: int = 1000):
    """[(w_shape, b_shape), ...] in layer order (stem; per block the 1x1
    reduce, the 3x3, the 1x1 expand and, in a stage's first block, the
    projection; the fc layer), at the published sizes unless others are
    given."""
    c = input_shape[-1]
    shapes = [((7, 7, c, stem), (stem,))]
    c = stem
    for n, mid in zip(blocks, widths):
        out = EXPANSION * mid
        for i in range(n):
            shapes += [((1, 1, c, mid), (mid,)), ((3, 3, mid, mid), (mid,)),
                       ((1, 1, mid, out), (out,))]
            if i == 0:
                shapes.append(((1, 1, c, out), (out,)))
            c = out
    shapes.append(((c, classes), (classes,)))
    return shapes


def forward(params, x, act=None):
    """Logits [N, classes] of images [N, H, W, C].  ``params`` is the list
    of (w, b) in layer order; ``act(i, h)``, when given, replaces the input
    ``h`` of parametric layer ``i`` (the lower-precision control uses it).
    A block whose expand changes the width has a projection, and every
    such block after the first is a stage entry with stride 2."""
    from perfbench.harness import plain
    act = act or (lambda i, h: h)
    w, b = params[0]
    h = plain.relu(plain.conv(act(0, x), w, b, stride=2))
    h = plain.maxpool(h, 3, 2, "SAME")
    i, first = 1, True
    while i < len(params) - 1:
        (w1, b1), (w2, b2), (w3, b3) = params[i:i + 3]
        project = w3.shape[-1] != h.shape[-1]
        s = 2 if project and not first else 1
        y = plain.relu(plain.conv(act(i, h), w1, b1))
        y = plain.relu(plain.conv(act(i + 1, y), w2, b2, stride=s))
        y = plain.conv(act(i + 2, y), w3, b3)
        i += 3
        skip = h
        if project:
            wp, bp = params[i]
            skip = plain.conv(act(i, h), wp, bp, stride=s)
            i += 1
        h = plain.relu(skip + y)
        first = False
    w, b = params[i]
    return plain.dense(act(i, plain.global_mean(h)), w, b)
