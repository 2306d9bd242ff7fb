"""Benchmark harness — one module per paper table/figure plus framework
microbenches.  Prints ``name,us_per_call,derived`` CSV.

  paper_table1     — §5.2 throughput reproduction (0.224 / 4.48 GOPS) +
                     Table 1 context + the TPU-adapted roofline comparison
  kernel_bench     — conv2d_ws banking sweep, int8 datapath, WS-GEMM blocks
  network_bench    — whole-network int8 executor (LeNet/VGG-small) vs the
                     §5.2 model's network prediction → BENCH_network.json
  attention_bench  — chunked-flash vs dense
  moe_bench        — EP dispatch statistics (drop rates, capacity)
  roofline_table   — the dry-run matrix (TPU numbers; see EXPERIMENTS.md)
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (attention_bench, kernel_bench, moe_bench,
                            network_bench, paper_table1, roofline_table)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    suites = [
        ("paper_table1", paper_table1.run),
        ("kernel_bench", kernel_bench.run),
        ("network_bench", network_bench.run),
        ("attention_bench", attention_bench.run),
        ("moe_bench", moe_bench.run),
        ("roofline_table", roofline_table.run),
    ]
    only = [a for a in sys.argv[1:] if not a.startswith("-")]
    failed = []
    for name, fn in suites:
        if only and name not in only:
            continue
        try:
            fn()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
