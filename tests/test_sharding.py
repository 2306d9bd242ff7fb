"""Distribution coherence on a small (2×4) debug mesh, in a subprocess so
the fake-device flag never leaks (smoke tests must see 1 device).

Checks: sharded train step == single-device train step (GSPMD is a
numerics-preserving transform up to reduction order), and the decode step
compiles + runs under the decode sharding rules (sequence-sharded cache).
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str) -> str:
    env = dict(os.environ, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=560)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    return r.stdout


def test_sharded_train_step_matches_single_device():
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config, reduce_config
        from repro.distributed.sharding import ShardingPlan
        from repro.launch.mesh import make_debug_mesh
        from repro.layers.common import materialize, shape_structs, ParamSpec
        from repro.models import lm
        from repro.optim.adamw import AdamWConfig, opt_state_specs
        from repro.train.train_step import make_train_step, init_state_specs

        cfg = reduce_config(get_config("llama3_8b"))
        sspecs = init_state_specs(cfg)
        state = {
            "params": materialize(sspecs["params"], jax.random.PRNGKey(0)),
            "opt": materialize(sspecs["opt"], jax.random.PRNGKey(1)),
            "step": jnp.zeros((), jnp.int32),
        }
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                    (4, 32)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, cfg.vocab_size,
                                                    (4, 32)), jnp.int32)}
        hp = AdamWConfig(warmup_steps=1, total_steps=10)

        # single device
        ref_step = jax.jit(make_train_step(cfg, hp))
        ref_state, ref_metrics = ref_step(state, batch)

        # sharded
        mesh = make_debug_mesh(2, 4)
        plan = ShardingPlan(mesh=mesh, fsdp=True, mode="train")
        full_specs = {"params": sspecs["params"], "opt": sspecs["opt"],
                      "step": ParamSpec((), (), dtype="int32", init="zeros")}
        st_sh = plan.param_shardings(full_specs)
        b_sh = plan.input_shardings(jax.tree.map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype), batch))
        with jax.set_mesh(mesh):
            sh_step = jax.jit(make_train_step(cfg, hp, act_rules=plan.acts),
                              in_shardings=(st_sh, b_sh),
                              out_shardings=(st_sh, None))
            state_d = jax.device_put(state, st_sh)
            batch_d = jax.device_put(batch, b_sh)
            new_state, metrics = sh_step(state_d, batch_d)
        np.testing.assert_allclose(float(metrics["loss"]),
                                   float(ref_metrics["loss"]),
                                   rtol=2e-4, atol=2e-4)
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
            a.astype(jnp.float32) - b.astype(jnp.float32)))),
            ref_state["params"], jax.device_get(new_state["params"]))
        worst = max(jax.tree.leaves(d))
        assert worst < 5e-3, worst
        print("TRAIN_SHARDED_OK", worst)
    """))
    assert "TRAIN_SHARDED_OK" in out


def test_sharded_decode_step_runs():
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config, reduce_config
        from repro.distributed.sharding import ShardingPlan
        from repro.launch.mesh import make_debug_mesh
        from repro.layers.common import materialize, shape_structs
        from repro.models import lm
        from repro.serving.serve_step import make_decode_step

        cfg = reduce_config(get_config("llama3_8b"))
        params = materialize(lm.param_specs(cfg), jax.random.PRNGKey(0))
        B, S = 4, 32
        cspecs = lm.cache_specs(cfg, B, S)
        mesh = make_debug_mesh(2, 4)
        plan = ShardingPlan(mesh=mesh, fsdp=False, mode="decode")
        p_sh = plan.param_shardings(lm.param_specs(cfg))
        c_sh = plan.cache_shardings(cspecs)
        with jax.set_mesh(mesh):
            cache = jax.tree.map(
                lambda s, sh: jax.device_put(
                    jnp.zeros(s.shape, jnp.dtype(s.dtype)), sh),
                cspecs, c_sh, is_leaf=lambda x: hasattr(x, "axes"))
            params_d = jax.device_put(params, p_sh)
            step = jax.jit(make_decode_step(cfg, act_rules=plan.acts),
                           donate_argnums=(1,))
            tok = jnp.zeros((B,), jnp.int32)
            pos = jnp.zeros((B,), jnp.int32)
            logits, cache = step(params_d, cache, tok, pos)
            logits2, cache = step(params_d, cache, tok + 1, pos + 1)
        assert logits.shape == (B, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits2)))
        print("DECODE_SHARDED_OK")
    """))
    assert "DECODE_SHARDED_OK" in out
