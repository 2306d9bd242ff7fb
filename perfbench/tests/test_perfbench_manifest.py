"""BENCHMARK.json's cells resolve by name to files of their own, and the
file keeps to the shape the benchmark's contract gives it."""

import os
import re

import pytest

from perfbench.harness import manifest

BENCH = manifest.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_from_its_files(workload):
    cell = manifest.cell(BENCH, workload)
    assert cell.config["name"] == next(
        w["config"] for w in BENCH["workloads"] if w["name"] == workload)
    assert cell.traffic["loop"] in ("closed", "open")
    assert "batch" in cell.traffic["engine"]
    ref = manifest.reference(cell.config_file)
    assert callable(ref.forward) and callable(ref.param_shapes)
    assert cell.config["limits"]["max_rel_err"] > 0
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(manifest.reader(m["name"]))
        assert m["moves"] in reported


def test_unknown_workload_is_refused():
    with pytest.raises(LookupError):
        manifest.cell(BENCH, "no.such-cell")


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [c["name"] for c in BENCH["configs"]]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
               for m in metrics)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(manifest.ROOT, c["file"]))
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
