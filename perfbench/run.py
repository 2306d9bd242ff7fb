"""Run one cell of the benchmark and print its result line.

    python3 perfbench/run.py --workload vgg16.offline-b32 --seed 7 \\
        --seconds 10 --trace 0

From the root of a checkout.  ``BENCHMARK.json`` names the cell's
configuration (``perfbench/configs/<name>.json`` with its plain reference
``<name>.py`` beside it), its traffic mix (``perfbench/traffic/<mix>.json``)
and its metrics (per-layer readers in ``perfbench/metrics/<metric>.py``).
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is the result as one JSON
object; the numbers compared for ``correct`` are also the last lines of
standard error, each beside its limit.  Without a TPU, or with fewer chips
than the cell asks for, the command prints no result and exits with 3.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no program under {ROOT}/src/repro", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    # The program closes over its weights.  Without this JAX compiles them
    # into the program as constants, so every seed is a new program that
    # the persistent cache cannot serve; with it they are arguments of one
    # program.  It takes effect only before JAX is first imported.
    os.environ["JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS"] = "1"
    # no TPU runtime logs under a fixed /tmp path shared between checkouts
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from perfbench.harness import device, manifest, runner
    try:
        cell = manifest.cell(manifest.load_benchmark(ROOT), args.workload)
    except LookupError as e:
        print(e, file=sys.stderr)
        return 2
    try:
        result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                            t_start=T_START)
    except device.NoAccelerator as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return 3
    runner.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
