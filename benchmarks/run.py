"""Benchmark orchestrator: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--seed 0] [--refresh]
  PYTHONPATH=src python -m benchmarks.run --only table4,fig4

Optimizer results are cached in artifacts/bench/results_seed<k>.json.
"""

from __future__ import annotations

import argparse
import time

from benchmarks import (ablations, fig4_pareto, insights, kernels_bench,
                        stability, table4_accuracy, table5_cost,
                        table6_models, table8_latency, table9_overhead)
from benchmarks.common import load_or_run

SUITES = {
    "table4": table4_accuracy.run,
    "table5": table5_cost.run,
    "table6": table6_models.run,
    "table8": table8_latency.run,
    "table9": table9_overhead.run,
    "fig4": fig4_pareto.run,
    "insights": insights.run,
    "kernels": kernels_bench.run,
    "ablations": ablations.run,
    "stability": stability.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--refresh", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    picked = [s.strip() for s in args.only.split(",") if s.strip()] or \
        list(SUITES)
    needs_results = any(s != "kernels" for s in picked)
    results = None
    if needs_results:
        t0 = time.time()
        results = load_or_run(args.seed, refresh=args.refresh)
        print(f"[bench] optimizer results ready ({time.time()-t0:.1f}s)")
    for name in picked:
        t0 = time.time()
        SUITES[name](seed=args.seed, results=results)
        print(f"[bench] {name} done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
