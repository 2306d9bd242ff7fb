"""Operation and byte counts of the benchmark's configurations, against
counts made by hand from the papers' shapes, and the configurations'
plans against their plain references."""

import json
import os

import numpy as np
import pytest

from perfbench.harness import counts, manifest, model

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
