"""The run as a whole, on the CPU at test size.

The look for a chip is skipped in-process (``require`` returns the CPU
device), and everything else of a run is driven: set-up, the window, the
comparison that decides ``correct``.  A sound program comes out correct;
the timed path with an answer altered where it is produced, or with two
answers of a batch given to each other's requests, and the int4 control in
the program's place, come out not correct.
"""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench.harness import device, manifest, runner

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RUN = os.path.join(manifest.BENCH_DIR, "run.py")


def _tiny_cell(workload):
    bench = manifest.load_benchmark()
    bench["configs"] = [{"name": n, "file": os.path.join(DATA, f"{n}_tiny.json")}
                        for n in ("vgg16", "unet")]
    return manifest.cell(bench, workload)


@pytest.fixture
def on_cpu(monkeypatch, tmp_path):
    """Run on the CPU device, with the compile cache in ``tmp_path`` and the
    process's cache settings put back afterwards."""
    monkeypatch.setitem(device.PEAKS, jax.devices()[0].device_kind,
                        device.PEAKS["TPU v5 lite"])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield dict(root=str(tmp_path), require=lambda n: jax.devices()[:n])
    for k, v in saved.items():
        jax.config.update(k, v)


def _run(workload, **kw):
    return runner.run(_tiny_cell(workload), 2**40 + 3, 1.0, False,
                      t_start=time.perf_counter(), **kw)


@pytest.mark.parametrize("workload", ["vgg16.offline-b32", "unet.offline-b4"])
def test_sound_program_is_correct(on_cpu, workload):
    result = _run(workload, **on_cpu)
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "check"
    assert result["metrics"]["images_per_s"]["value"] > 0
    assert result["metrics"]["setup_s"]["value"] > 0


def _break_program(monkeypatch, fault):
    """``make_int8_program`` with ``fault`` applied to every batch's
    answers where the program produces them."""
    from repro.core import network
    make = network.make_int8_program

    def broken(*args, **kw):
        program = make(*args, **kw)
        return jax.jit(lambda x: fault(program(x)))
    monkeypatch.setattr(network, "make_int8_program", broken)


def test_answer_altered_where_produced_is_not_correct(on_cpu, monkeypatch):
    # the first answer of every batch comes out negated
    _break_program(monkeypatch, lambda y: y.at[0].multiply(-1))
    result = _run("vgg16.offline-b32", **on_cpu)
    assert not result["correct"]
    c = result["check"]["max_rel_err"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("workload", ["vgg16.offline-b32",
                                      "vgg16.served-poisson",
                                      "unet.offline-b4"])
def test_answers_swapped_within_a_batch_are_not_correct(
        on_cpu, monkeypatch, workload):
    # the first two answers of every batch go to each other's requests
    _break_program(monkeypatch, lambda y: y.at[jnp.array([0, 1])].set(
        y[jnp.array([1, 0])]))
    result = _run(workload, **on_cpu)
    assert not result["correct"]
    c = result["check"]["max_centred_err"]
    assert c["value"] > c["limit"]


def test_int4_control_is_not_correct(on_cpu):
    sys.path.insert(0, manifest.BENCH_DIR)
    try:
        import control
    finally:
        sys.path.remove(manifest.BENCH_DIR)
    cell = _tiny_cell("vgg16.offline-b32")
    r = control.readings(cell, 5, 0.5, **on_cpu)
    limits = cell.config["limits"]
    for name, limit in limits.items():
        assert r["program"][name] <= limit
    assert any(r["control_int4"][n] > lim for n, lim in limits.items())
    assert r["fault_swapped"]["max_centred_err"] > limits["max_centred_err"]


def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "perfbench", "run.py"),
         "--workload", "vgg16.offline-b32", "--seed", str(2**31 + 9),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_command_without_tpu_exits_nonzero_without_result():
    p = _command(manifest.ROOT)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    _no_result(p.stdout)


def test_command_without_program_exits_nonzero_without_result(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(manifest.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(str(tmp_path))
    assert p.returncode != 0
    _no_result(p.stdout)
