"""Benchmark entry point: one module per paper table/figure.

  table4           — paper Table IV (4+ algorithms x k0 x 3 problems)
  fig1_convergence — paper Fig. 1 (k0 effect on iterations-to-converge)
  fig2_k0          — paper Fig. 2 (k0 effect on CR and wall time)
  fig3_alpha       — paper Fig. 3 (selection-fraction effect)
  engine           — scan vs legacy vs sharded vs async round engine
  participation    — in-engine alpha sweep (scan + sharded; one-psum check)
  async            — CR/objective vs max_staleness (stale-x̄ engine)
  wallclock        — time-to-target vs straggler severity (clock engine;
                     also writes BENCH_wallclock.json)
  kernels_bench    — collapsed-vs-unrolled round + FedGiA-vs-FedAvg cost
  roofline         — §Roofline table from the dry-run artifacts

Run everything:  PYTHONPATH=src python -m benchmarks.run
One section:     PYTHONPATH=src python -m benchmarks.run --only engine

Sections whose main() returns data are dumped, machine-readable, to
BENCH_engine.json (path: --json) under their section name — for the
engine section that is round/s for the scan, legacy, sharded and async
paths — so the benchmark trajectory is diffable/plottable instead of
scraped from stdout; CI uploads the file as an artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks import async_bench, engine_bench, fig1_convergence, fig2_k0
from benchmarks import fig3_alpha, kernels_bench, participation_bench
from benchmarks import roofline, table4, wallclock_bench
from repro.utils.compile_cache import use_compile_cache

SECTIONS = {
    "table4": table4.main,
    "fig1": fig1_convergence.main,
    "fig2": fig2_k0.main,
    "fig3": fig3_alpha.main,
    "engine": engine_bench.main,
    "participation": participation_bench.main,
    "async": async_bench.main,
    "wallclock": wallclock_bench.main,
    "kernels": kernels_bench.main,
    "roofline": roofline.main,
}


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(SECTIONS), default=None,
                    action="append",
                    help="run only the named section(s); repeatable "
                         "(e.g. --only engine --only kernels)")
    ap.add_argument("--json", default="BENCH_engine.json",
                    help="where to write the machine-readable engine "
                         "results (written when the engine section runs)")
    args = ap.parse_args()
    names = args.only if args.only else list(SECTIONS)
    results = {}
    for name in names:
        print(f"\n===== {name} =====")
        t0 = time.time()
        out = SECTIONS[name]()
        if out is not None:
            results[name] = out
        print(f"----- {name} done in {time.time()-t0:.1f}s -----")
    if results and args.json:
        with open(args.json, "w") as f:
            # sections return plain dict/list rows, but values may be
            # numpy scalars — coerce anything non-JSON to float/str
            json.dump(results, f, indent=2, sort_keys=True,
                      default=lambda o: float(o)
                      if hasattr(o, "__float__") else str(o))
        print(f"\nwrote {args.json} "
              f"({', '.join(sorted(results))})")


if __name__ == "__main__":
    main()
