"""Readings that the correctness limit of a configuration is set from.

    python3 perfbench/control.py --workload vgg16.offline-b32 \\
        --seeds 101,102,103 --seconds 2

For each seed, in one process: the cell is set up as a run sets it up,
serves its traffic for ``--seconds``, and its answers are compared with the
float32 reference (``program``, the numbers a run compares).  Then the
control, the reference itself computed at int4 (the precision below the
configuration's int8: weights and every parametric layer's input on
4-bit grids), is compared with the float32 reference over the whole image
pool by the same numbers (``control_int4``); and so are the program's
answers with pool images taken in pairs, each answer given to the other
image's request (``fault_swapped``).  A limit lies above every ``program``
reading and below the control's and the fault's.  One JSON line per seed;
the benchmark's own runs never run this.  Needs the chip, like a run.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell, seed: int, seconds: float, bits: int = 4,
             **setup_kw) -> dict:
    import numpy as np

    from perfbench.harness import check, model, runner
    st = runner.setup(cell, seed, t_start=time.perf_counter(), **setup_kw)
    rec = runner.measure(st, seed, seconds, trace=False)
    ref = runner.references(st, rec, seed)
    program = check.errors(rec.answers, rec.pool_idx, ref)
    fault = check.errors(check.swapped(rec.answers, rec.pool_idx),
                         rec.pool_idx, ref)
    weights = model.make_weights(st.ref_mod, st.cfg, seed)
    ref = dict(enumerate(check.reference_answers(st.ref_mod, weights,
                                                 st.pool)))
    low = check.lower_precision_answers(st.ref_mod, weights, st.calib,
                                        st.pool, bits)
    control = check.errors(dict(enumerate(low)), np.arange(len(low)), ref)
    return {"seed": seed, "program": program,
            f"control_int{bits}": control, "fault_swapped": fault,
            "compared": len(rec.answers), "failed": rec.failed}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS"] = "1"
    from perfbench.harness import manifest
    cell = manifest.cell(manifest.load_benchmark(ROOT), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload,
                          **readings(cell, seed, args.seconds)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
