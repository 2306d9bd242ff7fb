"""An offline cell's images/s with the program as its own entry points
build it, to set beside a run of the benchmark.

    python3 perfbench/shipped.py --workload vgg16.offline-b32 --seed 7 \\
        --seconds 20

A benchmark run passes the weights to the program as arguments
(``JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS=1``) and its per-tensor scales as
per-channel vectors, so that one compiled program serves every seed.  This
runs the same cell without either: ``quantize_network``'s own result, which
``make_int8_program`` closes over with its weights and scales compiled in
as constants.  One JSON line: images/s, set-up and the numbers compared.
The benchmark's own runs never run this.  Needs the chip, like a run.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from repro.core.network import quantize_network

    from perfbench.harness import manifest, runner
    cell = manifest.cell(manifest.load_benchmark(ROOT), args.workload)
    if cell.traffic["loop"] != "closed":
        ap.error("images/s is measured in the offline (closed-loop) cells")
    st = runner.setup(cell, args.seed, t_start=t_start,
                      quantize=quantize_network)
    rec = runner.measure(st, args.seed, args.seconds, trace=False)
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "program": "as shipped",
        "images_per_s": rec.completed_in_window() / args.seconds,
        "setup_s": st.setup_s,
        "check": runner.compare(st, rec, args.seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
