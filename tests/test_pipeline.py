"""Pipeline parallelism: GPipe schedule == sequential execution (fwd + bwd),
on a 4-stage debug mesh in a subprocess (fake devices must not leak)."""

import os
import subprocess
import sys
import textwrap

from repro.distributed.pipeline import bubble_fraction

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bubble_fraction():
    assert bubble_fraction(1, 8) == 0.0
    assert abs(bubble_fraction(4, 4) - 3 / 7) < 1e-9
    assert bubble_fraction(4, 28) < 0.1    # enough microbatches amortize


def test_pipeline_matches_sequential():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_apply

        mesh = jax.make_mesh((4,), ("stage",), devices=jax.devices()[:4])
        rng = np.random.default_rng(0)
        D = 16
        n_stages, n_micro, B = 4, 8, 32
        params = {"w": jnp.asarray(rng.normal(size=(n_stages, D, D)) * 0.3,
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(n_stages, D)) * 0.1,
                                   jnp.float32)}
        x = jnp.asarray(rng.normal(size=(B, D)), jnp.float32)

        def stage_fn(p, h):
            return jnp.tanh(h @ p["w"] + p["b"])

        def sequential(params, x):
            h = x
            for s in range(n_stages):
                h = stage_fn(jax.tree.map(lambda t: t[s], params), h)
            return h

        with jax.set_mesh(mesh):
            y_pipe = pipeline_apply(stage_fn, params, x, mesh=mesh,
                                    axis="stage", n_micro=n_micro)
        y_seq = sequential(params, x)
        np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_seq),
                                   rtol=2e-5, atol=2e-5)

        # gradients flow through the schedule identically
        def loss_pipe(p):
            return jnp.sum(jnp.square(pipeline_apply(
                stage_fn, p, x, mesh=mesh, axis="stage",
                n_micro=n_micro)))

        def loss_seq(p):
            return jnp.sum(jnp.square(sequential(p, x)))

        with jax.set_mesh(mesh):
            g1 = jax.grad(loss_pipe)(params)
        g2 = jax.grad(loss_seq)(params)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)
        print("PIPELINE_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=560)
    assert "PIPELINE_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-4000:]
