"""Operation and byte counts of the benchmark's configurations, against
counts made by hand from the papers' shapes, and the configurations'
plans against their plain references; the counts of every node kind the
program builds against the program's own shape walk, and of ResNet-50 v1.5
and MobileNetV2 against their published totals."""

import dataclasses
import json
import os

import numpy as np
import pytest

from perfbench.harness import counts, manifest, model
from repro.core import network

CONFIGS = os.path.join(manifest.BENCH_DIR, "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_vgg16_counts_match_hand_count():
    lc = {c.name: c for c in counts.layer_counts(_cfg("vgg16"))}
    # conv1_2: 224*224 outputs x 3*3 taps x 64 in x 64 out
    assert lc["conv1_2"].macs == 224 * 224 * 9 * 64 * 64
    # the fused pool shrinks what conv1_2 writes, not what it computes
    assert lc["conv1_2"].out_bytes == 112 * 112 * 64
    assert lc["fc6"].macs == 7 * 7 * 512 * 4096
    # the dequantized logits are float32
    assert lc["fc8"].out_bytes == 4 * 1000
    total = sum(c.macs for c in lc.values())
    assert total == 15_470_264_320          # the published 15.47 GMAC
    assert counts.useful_ops_per_image(_cfg("vgg16")) == 2 * total


def test_unet_counts_one_tap_per_upconv_output_pixel():
    lc = {c.name: c for c in counts.layer_counts(_cfg("unet"))}
    # up4: 32x32x1024 -> 64x64x512; each output pixel gets one of the 2x2
    # taps, so the useful work is out pixels x in x out channels
    assert lc["up4"].macs == 64 * 64 * 1024 * 512
    # dec4a reads the 1024-wide concat of the skip and the up-conv
    assert lc["dec4a"].macs == 64 * 64 * 9 * 1024 * 512
    assert lc["dec4a"].in_bytes == 64 * 64 * 1024
    assert lc["head"].out_bytes == 512 * 512 * 2 * 4
    total = sum(c.macs for c in lc.values())
    assert total == 192_384_335_872          # about 192 GMAC at 512x512


@pytest.mark.parametrize("name,params", [("vgg16", 138_357_544),
                                         ("unet", 31_030_658)])
def test_reference_parameters_match_plan(name, params):
    cfg = _cfg(name)
    ref = manifest.reference(os.path.join(CONFIGS, f"{name}.json"))
    shapes = ref.param_shapes(tuple(cfg["input_shape"]))
    assert sum(int(np.prod(w)) + int(np.prod(b)) for w, b in shapes) \
        == params
    plan = model.build_plan(cfg)
    fake = [(np.zeros(w, np.float32), np.zeros(b, np.float32))
            for w, b in shapes]
    model.program_params(plan, fake)         # raises on any mismatch
    assert [c.name for c in counts.layer_counts(cfg)] == [
        n for n, sp in zip(plan.node_names(), plan.layers)
        if sp.kind in counts.PARAM_KINDS]


def test_least_time_names_its_bound():
    c = counts.LayerCount("x", "conv", macs=10**9, in_bytes=10**6,
                          out_bytes=10**6, weight_bytes=10**6)
    t, bound = c.least_time(1, peak_ops=1e12, peak_bytes=1e12)
    assert (t, bound) == (2e-3, "compute")
    t, bound = c.least_time(1, peak_ops=1e15, peak_bytes=1e9)
    assert bound == "memory" and t == pytest.approx(3e-3)


# Per accepted configuration: its parametric layers, and the sums of their
# MACs, input, output and weight bytes, as the benchmark has counted them
# since the configurations were accepted.
PINNED = {
    "vgg16": ([f"conv{s}_{i}" for s, n in enumerate((2, 2, 3, 3, 3), 1)
               for i in range(1, n + 1)] + ["fc6", "fc7", "fc8"],
              15_470_264_320, 9_115_136, 8_968_608, 138_397_792),
    "unet": (["enc1a", "enc1b", "enc2a", "enc2b", "enc3a", "enc3b", "enc4a",
              "enc4b", "bott_a", "bott_b", "up4", "dec4a", "dec4b", "up3",
              "dec3a", "dec3b", "up2", "dec2a", "dec2b", "up1", "dec1a",
              "dec1b", "head"],
             192_384_335_872, 167_510_016, 161_480_704, 31_051_208),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_accepted_configs_count_as_pinned(name):
    lc = counts.layer_counts(_cfg(name))
    names, macs, in_bytes, out_bytes, weight_bytes = PINNED[name]
    assert [c.name for c in lc] == names
    assert (sum(c.macs for c in lc), sum(c.in_bytes for c in lc),
            sum(c.out_bytes for c in lc),
            sum(c.weight_bytes for c in lc)) == (macs, in_bytes, out_bytes,
                                                 weight_bytes)


def resnet50_v15():
    """ResNet-50 (He et al., arXiv:1512.03385, Table 1, 50-layer) at
    224x224x3 with v1.5's stride on each stage entry's 3x3 conv, batch norm
    folded into the conv biases, option-B projection shortcuts."""
    layers = [dict(kind="conv", features=64, kernel=7, stride=2,
                   name="conv1"),
              dict(kind="maxpool", size=3, stride=2, padding="SAME",
                   name="pool1")]
    src = "pool1"
    for stage, (mid, blocks, stride) in enumerate(
            [(64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2)], start=2):
        for i in range(blocks):
            s = stride if i == 0 else 1
            n = f"res{stage}{'abcdef'[i]}"
            layers += [
                dict(kind="conv", features=mid, kernel=1, name=f"{n}_1",
                     inputs=[src]),
                dict(kind="conv", features=mid, stride=s, name=f"{n}_2"),
                dict(kind="conv", features=4 * mid, kernel=1, relu=False,
                     name=f"{n}_3")]
            skip = src
            if i == 0:
                skip = f"{n}_proj"
                layers.append(dict(kind="conv", features=4 * mid, kernel=1,
                                   stride=s, relu=False, name=skip,
                                   inputs=[src]))
            layers.append(dict(kind="add", relu=True, name=n,
                               inputs=[skip, f"{n}_3"]))
            src = n
    layers += [dict(kind="global_pool", name="pool5"),
               dict(kind="dense", features=1000, name="fc")]
    return dict(name="resnet50_v1.5", input_shape=[224, 224, 3],
                layers=layers)


def mobilenet_v2():
    """MobileNetV2 1.0 (Sandler et al., arXiv:1801.04381, Table 2) at
    224x224x3, batch norm folded; ReLU6 is written as ReLU, which no count
    reads.  The last 1x1 conv over the pooled map is the dense head."""
    layers = [dict(kind="conv", features=32, stride=2, name="conv0")]
    src, c = "conv0", 32
    for i, (t, out, n, stride) in enumerate(
            [(1, 16, 1, 1), (6, 24, 2, 2), (6, 32, 3, 2), (6, 64, 4, 2),
             (6, 96, 3, 1), (6, 160, 3, 2), (6, 320, 1, 1)], start=1):
        for j in range(n):
            s = stride if j == 0 else 1
            b = f"block{i}_{j}"
            block = [dict(kind="depthwise", stride=s, name=f"{b}_dw"),
                     dict(kind="conv", features=out, kernel=1, relu=False,
                          name=f"{b}_project")]
            if t != 1:
                block.insert(0, dict(kind="conv", features=c * t, kernel=1,
                                     name=f"{b}_expand"))
            block[0]["inputs"] = [src]
            layers += block
            if s == 1 and c == out:
                layers.append(dict(kind="add", name=b,
                                   inputs=[src, f"{b}_project"]))
                src = b
            else:
                src = f"{b}_project"
            c = out
    layers += [dict(kind="conv", features=1280, kernel=1, name="conv_last",
                    inputs=[src]),
               dict(kind="global_pool", name="pool"),
               dict(kind="dense", features=1000, name="fc")]
    return dict(name="mobilenet_v2", input_shape=[224, 224, 3],
                layers=layers)


def _param_sizes(cfg):
    """{layer: (weights, biases)} from each layer's kernel, its features
    and the width of the map it reads, tracked here apart from the counts."""
    width = {counts.INPUT: cfg["input_shape"][-1]}
    prev, sizes = width[counts.INPUT], {}
    for sp in cfg["layers"]:
        c = width[sp["inputs"][0]] if sp.get("inputs") else prev
        k, f = sp.get("kernel", 3), sp.get("features", c)
        if sp["kind"] == "conv":
            sizes[sp["name"]] = (k * k * c * f, f)
        elif sp["kind"] == "depthwise":
            sizes[sp["name"]] = (k * k * f, f)
        elif sp["kind"] == "dense":
            sizes[sp["name"]] = (c * f, f)
        else:
            f = c
        width[sp["name"]] = prev = f
    return sizes


@pytest.mark.parametrize("make,macs,params", [
    # fvcore's 4.09 G; torchvision's 25,557,032 less 26,560 batch-norm
    # parameters that folding leaves as conv biases
    (resnet50_v15, 4_089_184_256, 25_530_472),
    # the paper's "300M MAdds"
    (mobilenet_v2, 300_774_272, 3_487_816),
])
def test_published_anchors(make, macs, params):
    cfg = make()
    lc = counts.layer_counts(cfg)
    sizes = _param_sizes(cfg)
    assert sum(c.macs for c in lc) == macs
    assert [c.name for c in lc] == list(sizes)
    assert sum(w + b for w, b in sizes.values()) == params
    for c in lc:                        # int8 weights, int32 biases
        w, b = sizes[c.name]
        assert c.weight_bytes == w + 4 * b, c.name


def test_resnet50_stem_pool_and_strides():
    shapes = {name: shape for name, shape, _ in
              counts.walk(resnet50_v15())}
    assert shapes["conv1"] == (112, 112, 64)
    assert shapes["pool1"] == (56, 56, 64)          # 3x3/2 SAME
    assert shapes["res3a"] == (28, 28, 512)
    assert shapes["res5c"] == (7, 7, 2048)
    assert shapes["pool5"] == (2048,)
    lc = {c.name: c for c in counts.layer_counts(resnet50_v15())}
    # v1.5: the stride on the 3x3, so the 1x1 reduce reads the full map
    assert lc["res3a_1"].macs == 56 * 56 * 256 * 128
    assert lc["res3a_2"].macs == 28 * 28 * 9 * 128 * 128
    assert lc["fc"].out_bytes == 4 * 1000


def test_pool_defaults_to_window_stride_and_valid():
    cfg = {"input_shape": [7, 9, 2], "layers": [
        {"kind": "maxpool", "name": "p"},
        {"kind": "avgpool", "size": 3, "stride": 1, "name": "q"},
        {"kind": "flatten", "name": "f"},
        {"kind": "dense", "features": 4, "name": "d"}]}
    shapes = [shape for _, shape, _ in counts.walk(cfg)]
    assert shapes == [(3, 4, 2), (1, 2, 2), (4,), (4,)]


def _as_config(plan):
    """``plan`` as a configuration file holds it: each node by the name of
    its constructor in ``repro.core.network`` and that constructor's
    arguments, under the name the program gives the node."""
    kinds = {"pool": "maxpool", "globalpool": "global_pool"}
    layers = []
    for name, sp in zip(plan.node_names(), plan.layers):
        kind = kinds.get(sp.kind, sp.kind)
        if kind == "conv" and sp.groups == network.DEPTHWISE:
            kind = "depthwise"
        d = {"kind": kind, "name": name}
        if kind in ("conv", "depthwise", "conv_transpose"):
            (k, kw) = sp.kernel
            assert k == kw
            d.update(features=sp.features, kernel=k, stride=sp.stride,
                     padding=sp.padding, relu=sp.relu, pool=sp.pool)
            if kind != "depthwise":
                d.update(groups=sp.groups, dilation=sp.dilation)
        elif kind == "dense":
            d.update(features=sp.features, relu=sp.relu)
        elif kind in ("maxpool", "avgpool"):
            d["size"] = sp.size
        elif kind == "add":
            d["relu"] = sp.relu
        if sp.inputs:
            d["inputs"] = list(sp.inputs)
        layers.append(d)
    return json.loads(json.dumps({"name": plan.name,
                                  "input_shape": list(plan.input_shape),
                                  "layers": layers}))


ZOO = ("lenet", "vgg_small", "vgg_imagenet", "large_map", "resnet_small",
       "resnet_bottleneck", "mobilenet_small", "mobilenet_v2ish",
       "unet_small", "dilated_context")


@pytest.mark.parametrize("name", ZOO + ("mobilenet_v2",))
def test_counts_follow_the_programs_shape_walk(name):
    if name == "mobilenet_v2":
        cfg = mobilenet_v2()
        plan = model.build_plan(cfg)
    else:
        plan = getattr(network, name)()
        cfg = _as_config(plan)
        assert model.build_plan(cfg).layers == tuple(
            dataclasses.replace(sp, name=n)
            for n, sp in zip(plan.node_names(), plan.layers))
    walked = counts.walk(cfg)
    assert [n for n, _, _ in walked] == plan.node_names()
    assert [shape for _, shape, _ in walked] == plan.activation_shapes()
    assert [c.name for c in counts.layer_counts(cfg)] == [
        n for n, sp in zip(plan.node_names(), plan.layers)
        if sp.kind in network.PARAM_KINDS]


def test_every_node_kind_builds_and_counts():
    cfg = {"name": "every_kind", "input_shape": [16, 16, 8], "layers": [
        {"kind": "conv", "features": 16, "name": "c1"},
        {"kind": "conv", "features": 16, "groups": 4, "dilation": 2,
         "padding": "VALID", "name": "g1"},                     # 12x12
        {"kind": "depthwise", "stride": 2, "name": "dw",
         "inputs": ["c1"]},                                     # 8x8
        {"kind": "conv", "features": 16, "groups": -1, "kernel": 5,
         "padding": "VALID", "pool": True, "name": "dw5"},      # 2x2
        {"kind": "conv_transpose", "features": 16, "name": "up",
         "inputs": ["dw"]},                                     # 16x16
        {"kind": "add", "relu": True, "name": "sum",
         "inputs": ["up", "c1"]},
        {"kind": "concat", "name": "cat", "inputs": ["sum", "input"]},
        {"kind": "maxpool", "name": "mp"},                      # 8x8x24
        {"kind": "avgpool", "size": 2, "name": "ap"},           # 4x4x24
        {"kind": "global_pool", "name": "gp"},
        {"kind": "dense", "features": 10, "name": "fc_a"},
        {"kind": "flatten", "name": "fl", "inputs": ["ap"]},
        {"kind": "dense", "features": 10, "name": "fc_b"},
        {"kind": "add", "name": "logits", "inputs": ["fc_a", "fc_b"]}]}
    plan = model.build_plan(cfg)
    assert {sp.kind for sp in plan.layers} == {
        "conv", "conv_transpose", "pool", "avgpool", "globalpool",
        "flatten", "dense", "add", "concat"}
    walked = counts.walk(cfg)
    assert [shape for _, shape, _ in walked] == plan.activation_shapes()
    assert walked[1][1] == (12, 12, 16) and walked[3][1] == (2, 2, 16)
    lc = {c.name: c for c in counts.layer_counts(cfg)}
    assert lc["g1"].macs == 12 * 12 * 9 * (16 // 4) * 16
    assert lc["g1"].weight_bytes == 9 * 4 * 16 + 4 * 16
    # depthwise: one input channel per output, the count of a 16-group conv
    assert lc["dw"].macs == 8 * 8 * 9 * 16
    assert lc["dw"].weight_bytes == 9 * 16 + 4 * 16
    assert lc["dw5"].macs == 4 * 4 * 25 * 16          # before the fused pool
    assert lc["fc_b"].out_bytes == 4 * 10             # the last parametric
    assert set(lc) == {"c1", "g1", "dw", "dw5", "up", "fc_a", "fc_b"}


@pytest.mark.parametrize("layer,match", [
    ({"kind": "lrn", "name": "x"}, "no count for layer kind"),
    ({"kind": "add", "name": "x", "inputs": ["input", "c"]}, "add takes"),
    ({"kind": "conv", "features": 6, "groups": 4, "name": "x"}, "groups"),
    ({"kind": "maxpool", "padding": "FULL", "name": "x"}, "padding"),
])
def test_what_cannot_be_counted_raises(layer, match):
    cfg = {"input_shape": [8, 8, 4], "layers": [
        {"kind": "conv", "features": 8, "name": "c"}, layer,
        {"kind": "dense", "features": 2, "name": "fc"}]}
    with pytest.raises(ValueError, match=match):
        counts.walk(cfg)
