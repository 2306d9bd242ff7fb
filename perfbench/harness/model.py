"""Build a configuration's program from its file and the seed.

The plan comes from the program's public constructors
(``repro.core.network``); weights and images are made by the benchmark on
the device, in one jitted call each, from the seed; the program's own
``quantize_network`` lowers the weights to int8, run as one jitted call.
"""

from __future__ import annotations

from typing import List

import numpy as np

# independent streams drawn from one seed
WEIGHTS, CALIBRATION, POOL = 0, 1, 2


def seed_key(seed: int, stream: int):
    """A JAX key for ``stream`` of ``seed``; any non-negative integer seed,
    wider than 32 bits too, maps to its own key."""
    import jax
    state = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(state, np.uint32),
                                    impl="threefry2x32")


def build_plan(cfg: dict):
    """The ``NetworkPlan`` that ``cfg["layers"]`` describes: each layer's
    ``kind`` names its ``repro.core.network`` constructor; a merge (``add``,
    ``concat``) takes its branches from ``inputs``, any other node at most
    one input from there."""
    from repro.core import network
    layers = []
    for sp in cfg["layers"]:
        args = {k: v for k, v in sp.items() if k not in ("kind", "inputs")}
        kind = sp["kind"]
        if kind in ("add", "concat"):
            layers.append(getattr(network, kind)(*sp["inputs"], **args))
            continue
        if sp.get("inputs"):
            (args["input"],) = sp["inputs"]
        layers.append(getattr(network, kind)(**args))
    return network.NetworkPlan(name=cfg["name"],
                               input_shape=tuple(cfg["input_shape"]),
                               layers=tuple(layers))


def make_weights(ref_mod, cfg: dict, seed: int):
    """[(w, b), ...] float32 on the device, in the reference's layer order."""
    import jax

    from perfbench.harness import plain
    shapes = ref_mod.param_shapes(tuple(cfg["input_shape"]))
    init = jax.jit(lambda key: plain.he_shapes_init(key, shapes))
    return init(seed_key(seed, WEIGHTS))


def make_images(cfg: dict, seed: int, stream: int, n: int) -> np.ndarray:
    """``n`` standard-normal float32 images of the configuration, on the
    host, drawn on the device from ``stream`` of ``seed``."""
    import jax
    import jax.numpy as jnp
    shape = (n, *cfg["input_shape"])
    draw = jax.jit(lambda key: jax.random.normal(key, shape, jnp.float32))
    return np.asarray(draw(seed_key(seed, stream)))


def program_params(plan, weights) -> List:
    """The program's per-node parameter list (None for nodes without
    weights) from the reference-ordered ``weights``; the shapes must agree
    with the program's own walk of the plan."""
    it = iter(weights)
    params = []
    for shp in plan.param_shapes():
        if shp is None:
            params.append(None)
            continue
        w, b = next(it)
        if (tuple(w.shape), tuple(b.shape)) != (shp["w"], shp["b"]):
            raise ValueError(f"reference weights {w.shape}/{b.shape} do not "
                             f"match the plan's {shp['w']}/{shp['b']}")
        params.append({"w": w, "b": b})
    if next(it, None) is not None:
        raise ValueError("the reference has more parametric layers than "
                         "the plan")
    return params


def quantize(plan, params, calib):
    """``quantize_network(plan, params, calib)`` as one jitted call.

    Each per-tensor scale comes back broadcast to a vector over the
    channels it scales, the form the program also takes for per-channel
    scales: the same products, but no seed-dependent scalar that JAX would
    compile into the program as a literal, so every seed runs one program
    that the persistent cache holds."""
    import jax
    import jax.numpy as jnp

    from repro.core import network
    shapes = [tuple(plan.input_shape)] + plan.activation_shapes()
    ins = plan.resolved_inputs()
    last = max(i for i, sp in enumerate(plan.layers)
               if sp.kind in network.PARAM_KINDS)

    def channels(s, j):                 # j: node index, -1 the input
        return None if s is None else jnp.broadcast_to(s, shapes[j + 1][-1:])

    def fields(params, calib):
        q = network.quantize_network(plan, params, calib)
        merges = tuple(None if m is None else tuple(
            channels(s, j) for s, j in zip(m, ins[i]))
            for i, m in enumerate(q.merge_scales))
        return (q.weights, q.biases,
                tuple(channels(r, i) for i, r in enumerate(q.requants)),
                channels(q.in_scale, -1), channels(q.out_dequant, last),
                merges)

    w, b, rq, s_in, s_out, merges = jax.jit(fields)(params, calib)
    return network.QuantizedNetwork(plan, w, b, rq, s_in, s_out,
                                    merge_scales=merges)


def plan_lines(plan, counts, batch: int, peak: dict) -> List[str]:
    """One line per conv/dense layer: the tile plan the program runs (tiles,
    banks, kernel variant, VMEM working set) and the layer's least time per
    batch with its bound."""
    from repro.core.convcore import ConvCoreConfig
    from repro.core.network import program_tile_plans
    tps = program_tile_plans(plan, ConvCoreConfig(int8=True))
    by_name = {lc.name: lc for lc in counts}
    lines = []
    for name, sp, tp in zip(plan.node_names(), plan.layers, tps):
        lc = by_name.get(name)
        if lc is None:
            continue
        t, bound = lc.least_time(batch, peak["int8_ops_per_s"],
                                 peak["hbm_bytes_per_s"])
        if tp is not None:
            how = (f"{'tiled' if tp.tiled else 'whole map'} "
                   f"h_tile {tp.h_tile} x {tp.n_h_tiles}, banks "
                   f"{tp.cin_banks}x{tp.kout_banks}, "
                   f"{'conv2d_ws_pipe' if tp.pipelined else 'conv2d_ws'}, "
                   f"VMEM {tp.working_set_bytes / 2**20:.2f} MiB")
        else:
            how = "matmul_ws"
        lines.append(f"layer {name} ({sp.kind}): {how}; "
                     f"{lc.ops * batch / 1e9:.3f} G useful ops per batch, "
                     f"least time {t * 1e6:.1f} us ({bound}-bound)")
    return lines
