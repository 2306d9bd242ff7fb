"""Offered-load sweep of an open-loop cell, to find its knee.

    python3 perfbench/sweep.py --workload vgg16.served-poisson --seed 5 \\
        --seconds 5 --rates 200,400,800

One set-up, then one window per rate with the cell's traffic at that rate.
Per rate one JSON line: requests offered, the share answered by the
window's end, the engine's queue depth one second in and at the end, the
latency median and 95th percentile, and how late the generator ran.  The
knee is the highest rate at which at least 98% are answered in the window
and the queue at the end is no deeper than one second in (or than one
batch).  The cell's traffic file then fixes a rate below it.  Needs the
chip, like a run; the benchmark's own runs never run this.
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
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS"] = "1"
    from perfbench.harness import manifest, runner, traffic
    cell = manifest.cell(manifest.load_benchmark(ROOT), args.workload)
    st = runner.setup(cell, args.seed, t_start=time.perf_counter())
    for n, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(st.mix, rate_per_s=rate)
        rec = traffic.run(st.engine, st.pool, mix, args.seed + n,
                          args.seconds, trace=False)
        lat = traffic.latencies_ms(rec, float(mix["grace_s"]))
        late = (rec.sent_ns - rec.due_ns) / 1e6
        print(json.dumps({
            "rate_per_s": rate, "offered": rec.attempted,
            "answered_in_window": rec.completed_in_window() / rec.attempted,
            "failed": rec.failed,
            "queue_depth_1s": rec.queue_depth_start,
            "queue_depth_end": rec.queue_depth_end,
            "latency_p50_ms": traffic.percentile(lat, 50),
            "latency_p95_ms": traffic.percentile(lat, 95),
            "gen_late_p95_ms": traffic.percentile(late, 95)}), flush=True)
    st.engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
