"""Hypothesis invariants for the plan autotuner (ISSUE 7 satellite):
over random legal layer shapes, the chosen plan always fits VMEM,
respects group-aligned banks, is never worse than the greedy
``plan_tiles`` plan under the same model, and is deterministic."""

import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import banking  # noqa: E402
from repro.core.autotune import autotune_layer, plan_cost  # noqa: E402


@st.composite
def _layer_shapes(draw):
    groups = draw(st.sampled_from([1, 1, 1, 2, 4]))
    cgrp = draw(st.sampled_from([1, 2, 4, 8]))
    kg = draw(st.sampled_from([1, 2, 4, 8]))
    h = draw(st.integers(6, 40))
    w = draw(st.integers(6, 40))
    kh = draw(st.sampled_from([1, 3]))
    pool = draw(st.booleans())
    stride = draw(st.sampled_from([1, 2]))
    return dict(h=h, w=w, c=cgrp * groups, k=kg * groups, kh=kh,
                stride=stride, padding="SAME", groups=groups,
                pool=pool and kh == 3 and stride == 1)


@settings(max_examples=30, deadline=None)
@given(shape=_layer_shapes(),
       budget=st.sampled_from([64 * 1024, 512 * 1024,
                               banking.VMEM_LIMIT_BYTES]))
def test_autotuned_plan_fits_and_respects_groups(shape, budget):
    lt = autotune_layer(**shape, vmem_budget=budget)
    tp = lt.plan
    assert tp.fits_vmem or not lt.greedy_plan.fits_vmem, (
        "tuned plan busts VMEM even though candidates were pruned")
    # group alignment: cin banks divide the per-group slice, kout banks
    # are group-aligned divisors of K
    g = shape["groups"]
    assert (shape["c"] // g) % tp.cin_banks == 0
    assert shape["k"] % tp.kout_banks == 0
    assert tp.kout_banks % g == 0 or tp.kout_banks <= g


@settings(max_examples=30, deadline=None)
@given(shape=_layer_shapes())
def test_autotuned_never_worse_than_greedy(shape):
    lt = autotune_layer(**shape)
    assert lt.cycles <= lt.greedy_cycles
    # plan_cost agrees with the stored verdict, priced for the kernel
    # variant the plan carries
    assert plan_cost(lt.plan, lt.psums) == lt.cycles
    assert lt.plan.pipelined == (lt.plan.n_slabs > 1)


@settings(max_examples=15, deadline=None)
@given(shape=_layer_shapes())
def test_autotune_deterministic_given_table(shape):
    a = autotune_layer(**shape)
    b = autotune_layer(**shape)
    assert a == b
