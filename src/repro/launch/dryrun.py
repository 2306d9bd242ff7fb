"""Multi-pod dry run: lower + compile every (architecture × shape × mesh)
cell from ShapeDtypeStructs only (no allocation), and extract the roofline
terms from the compiled artifact.

MUST set the fake-device flag before any other import — jax locks the
device count on first init.  A CPU tool: it pins ``JAX_PLATFORMS=cpu``
for itself and the one child process it starts per cell, so no process
of it ever claims an accelerator (a chip belongs to one process at a
time, and this parent has touched JAX).
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"

# Dump the module right after SPMD partitioning: that HLO carries the TRUE
# tensor dtypes (bf16 collectives) and per-device shapes.  The final CPU
# executable is float-normalized (bf16→f32 everywhere), which would double
# the roofline's collective/memory byte counts vs. a real TPU lowering.
_DUMP_DIR = tempfile.mkdtemp(prefix="repro_spmd_dump_")
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    f"--xla_dump_to={_DUMP_DIR} "
    "--xla_dump_hlo_pass_re=spmd-partitioning "
    + os.environ.get("XLA_FLAGS", ""))

# ruff: noqa: E402
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (ALIASES, ARCH_NAMES, SHAPES, get_config,
                                shape_applicable)
from repro.distributed import sharding
from repro.distributed.sharding import ShardingPlan
from repro.launch.mesh import make_production_mesh
from repro.layers.common import ParamSpec, shape_structs
from repro.models import lm
from repro.optim.adamw import AdamWConfig, opt_state_specs
from repro.roofline import hlo as hlo_lib
from repro.roofline.analysis import build_report
from repro.serving.serve_step import make_decode_step, make_prefill_step
from repro.train.train_step import make_train_step


def _state_specs(cfg):
    pspecs = lm.param_specs(cfg)
    return {
        "params": pspecs,
        "opt": opt_state_specs(pspecs),
        "step": ParamSpec((), (), dtype="int32", init="zeros"),
    }


def _mem_analysis_dict(compiled):
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
        out["total_hbm_bytes"] = (out.get("argument_size_in_bytes", 0)
                                  + out.get("output_size_in_bytes", 0)
                                  + out.get("temp_size_in_bytes", 0)
                                  - out.get("alias_size_in_bytes", 0))
    except Exception as e:  # pragma: no cover - backend specific
        out["error"] = str(e)
    return out


DEFAULT_ACCUM = 4   # microbatches for train cells (memory fit — DESIGN.md)

# Per-arch microbatch tuning (§Perf A3/A5): under SP + selective remat the
# smaller dense models fit at accum 2, and fewer microbatch loops measurably
# reduces collective wire (remat × accum interact — see EXPERIMENTS.md).
ACCUM_BY_ARCH = {
    "llama3_8b": 2,
    "llama3p2_3b": 2,
    "gemma_7b": 2,
    "seamless_m4t_medium": 2,
    "deepseek_moe_16b": 2,
    # qwen3-moe and rwkv6 measured better at accum 2 (MFU 2×) but exceed
    # the 16 GB budget there (16.6 / 17.5 GB) — kept at 4; see EXPERIMENTS.
}


def lower_cell(arch: str, shape_name: str, mesh_kind: str,
               serve_dtype: str = "bfloat16", accum_steps: int = None,
               overrides: dict = None):
    """Builds and lowers one cell; returns (lowered, cfg, shape, mesh, plan)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise SystemExit(f"SKIP {arch}×{shape_name}: {why}")
    if accum_steps is None:
        default = ACCUM_BY_ARCH.get(ALIASES.get(arch, arch), DEFAULT_ACCUM)
        accum_steps = int(os.environ.get("REPRO_ACCUM", default)) \
            if shape.kind == "train" else 1

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    mode = shape.kind if shape.kind != "train" else "train"
    # residual-stream sequence sharding (§Perf A2): valid only when no block
    # mixes along time sequentially (recurrent archs keep seq local)
    from repro.configs.base import BLOCK_ATTN, BLOCK_LOCAL
    seq_shard = (os.environ.get("REPRO_SEQ_SHARD", "1") == "1" and shape.kind == "train"
                 and all(b in (BLOCK_ATTN, BLOCK_LOCAL)
                         for b in cfg.layer_pattern))
    plan = ShardingPlan(mesh=mesh, fsdp=(shape.kind == "train"), mode=mode,
                        seq_shard=seq_shard)

    if shape.kind == "train":
        # save_block_outputs is cheap only under SP (S/16-sized saves);
        # recurrent archs (no SP) use full recompute to fit HBM
        default_remat = "save_block_outputs" if seq_shard else "full"
        cfg = dataclasses.replace(
            cfg, remat_policy=os.environ.get("REPRO_REMAT", default_remat))
    else:
        cfg = dataclasses.replace(cfg, param_dtype=serve_dtype,
                                  remat_policy="none")
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            sspecs = _state_specs(cfg)
            state = shape_structs(sspecs)
            state_sh = plan.param_shardings(sspecs)
            batch = lm.input_specs(cfg, shape)
            batch_sh = plan.input_shardings(batch)
            step_fn = make_train_step(cfg, AdamWConfig(), act_rules=plan.acts,
                                      accum_steps=accum_steps)
            lowered = jax.jit(step_fn,
                              in_shardings=(state_sh, batch_sh),
                              out_shardings=(state_sh, None),
                              donate_argnums=(0,)).lower(state, batch)
        elif shape.kind == "prefill":
            pspecs = lm.param_specs(cfg)
            params = shape_structs(pspecs, dtype_override=serve_dtype)
            params_sh = plan.param_shardings(pspecs)
            batch = lm.input_specs(cfg, shape)
            batch_sh = plan.input_shardings(batch)
            step_fn = make_prefill_step(cfg, act_rules=plan.acts)
            lowered = jax.jit(step_fn,
                              in_shardings=(params_sh, batch_sh)
                              ).lower(params, batch)
        else:  # decode
            # §Perf C: the paper's 8-bit datapath applied to serving —
            # w8 weights (REPRO_W8=1) and int8 KV cache (REPRO_KV8=1)
            w8 = (os.environ.get("REPRO_W8") == "1" and cfg.moe is None
                  and all(b in (BLOCK_ATTN, BLOCK_LOCAL)
                          for b in cfg.layer_pattern))
            if os.environ.get("REPRO_KV8") == "1":   # int8 cache: any arch
                cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
            pspecs = lm.param_specs(cfg)
            if w8:
                from repro.core.quantize import quantize_weight_specs
                pspecs = quantize_weight_specs(pspecs)
                params = shape_structs(pspecs)
            else:
                params = shape_structs(pspecs, dtype_override=serve_dtype)
            params_sh = plan.param_shardings(pspecs)
            cspecs = lm.cache_specs(cfg, shape.global_batch, shape.seq_len)
            cache = shape_structs(cspecs)
            cache_sh = plan.cache_shardings(cspecs)
            inp = lm.input_specs(cfg, shape)
            inp_sh = plan.input_shardings(inp)
            step_fn = make_decode_step(cfg, act_rules=plan.acts)
            lowered = jax.jit(step_fn,
                              in_shardings=(params_sh, cache_sh,
                                            inp_sh["token"], inp_sh["pos"]),
                              donate_argnums=(1,)
                              ).lower(params, cache, inp["token"], inp["pos"])
    return lowered, cfg, shape, mesh, plan


def _spmd_dump_text() -> str:
    """Newest/largest post-SPMD-partitioning dump (dtype-exact HLO)."""
    best, size = None, -1
    for name in os.listdir(_DUMP_DIR):
        if "after_spmd-partitioning" in name and name.endswith(".txt"):
            p = os.path.join(_DUMP_DIR, name)
            s = os.path.getsize(p)
            if s > size:
                best, size = p, s
    if best is None:
        return ""
    with open(best) as f:
        return f.read()


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             keep_hlo: bool = False) -> dict:
    t0 = time.time()
    lowered, cfg, shape, mesh, plan = lower_cell(arch, shape_name, mesh_kind)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    chips = mesh.devices.size
    dump = _spmd_dump_text()
    hlo_source = "spmd_dump" if dump else "final_executable"
    txt = dump or compiled.as_text()
    costs = hlo_lib.analyze(txt)
    ca = compiled.cost_analysis() or {}
    report = build_report(arch, shape_name, mesh_kind, chips, costs,
                          cfg, shape, xla_flops=float(ca.get("flops", 0.0)))

    cell = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": chips, "fsdp": plan.fsdp, "mode": plan.mode,
        "hlo_source": hlo_source,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory_analysis": _mem_analysis_dict(compiled),
        "cost_analysis": {k: float(v) for k, v in ca.items()
                          if not k.startswith("utilization")},
        "collectives": {k: float(v) for k, v in costs.coll_bytes.items()},
        "collective_counts": {k: int(v) for k, v in costs.coll_counts.items()},
        "roofline": report.as_dict(),
    }
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
    with open(path, "w") as f:
        json.dump(cell, f, indent=1)
    if keep_hlo:
        with open(path.replace(".json", ".hlo.txt"), "w") as f:
            f.write(txt)
    print(f"OK {arch} × {shape_name} × {mesh_kind}: "
          f"compile {t_compile:.1f}s  "
          f"bottleneck={report.bottleneck}  "
          f"terms(c/m/x)=({report.t_compute:.4f}/{report.t_memory:.4f}/"
          f"{report.t_collective:.4f})s  "
          f"mfu@roofline={report.mfu_at_roofline:.3f}")
    return cell


def iter_cells(meshes=("single", "multi")):
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        for shape_name, shape in SHAPES.items():
            ok, why = shape_applicable(cfg, shape)
            for mesh_kind in meshes:
                yield arch, shape_name, mesh_kind, ok, why


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", help="architecture id (see configs)", default=None)
    p.add_argument("--shape", choices=sorted(SHAPES), default=None)
    p.add_argument("--mesh", choices=("single", "multi"), default="single")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--all", action="store_true",
                   help="run every runnable cell (subprocess per cell, "
                        "resumable via existing JSONs)")
    p.add_argument("--keep-hlo", action="store_true")
    p.add_argument("--force", action="store_true")
    args = p.parse_args()

    if args.all:
        failures = []
        skips = []
        for arch, shape_name, mesh_kind, ok, why in iter_cells():
            path = os.path.join(args.out,
                                f"{arch}__{shape_name}__{mesh_kind}.json")
            if not ok:
                skips.append((arch, shape_name, mesh_kind, why))
                continue
            if os.path.exists(path) and not args.force:
                print(f"cached {path}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name,
                   "--mesh", mesh_kind, "--out", args.out]
            if args.keep_hlo:
                cmd.append("--keep-hlo")
            r = subprocess.run(cmd)
            if r.returncode != 0:
                failures.append((arch, shape_name, mesh_kind))
        # record skips for the roofline table
        with open(os.path.join(args.out, "skips.json"), "w") as f:
            json.dump([{"arch": a, "shape": s, "mesh": m, "reason": w}
                       for a, s, m, w in skips], f, indent=1)
        print(f"done; {len(failures)} failures, {len(skips)} skips")
        if failures:
            for f_ in failures:
                print("FAILED:", f_)
            sys.exit(1)
        return

    arch = ALIASES.get(args.arch, args.arch)
    try:
        run_cell(arch, args.shape, args.mesh, args.out,
                 keep_hlo=args.keep_hlo)
    except SystemExit:
        raise
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
