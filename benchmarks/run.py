"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only fig7,...]``
prints ``name,us_per_call,derived`` CSV covering:
  fig2/fig3  compression entropy + ratios        (benchmarks/compression.py)
  fig4       decompress-vs-I/O overlap           (benchmarks/overlap.py)
  fig8       throughput vs batch size            (benchmarks/throughput.py)
  fig9       end-to-end latency vs output len    (benchmarks/e2e.py)
  fig10      cache-management ablation           (benchmarks/ablation.py)
  thm31      scheduler approximation bound       (benchmarks/scheduler_bound.py)
  splice     recovery→GEMM staging microbench    (benchmarks/splice.py)
  planner    §3.4 plan_pools online-speed bench  (benchmarks/planner_bench.py)
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import Rows

MODULES = {
    "fig23": "benchmarks.compression",
    "fig4": "benchmarks.overlap",
    "fig8": "benchmarks.throughput",
    "fig9": "benchmarks.e2e",
    "fig10": "benchmarks.ablation",
    "thm31": "benchmarks.scheduler_bound",
    "splice": "benchmarks.splice",
    "planner": "benchmarks.planner_bench",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(MODULES))
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(MODULES)
    rows = Rows()
    import importlib
    for name in names:
        mod = importlib.import_module(MODULES[name])
        t0 = time.time()
        mod.run(rows)
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    rows.emit()


if __name__ == '__main__':
    main()
