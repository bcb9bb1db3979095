"""Readings that the comparison limits are set from (not run by the
benchmark's own runs).

    python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--control 1]

For each seed, in one process: the cell's problem and its first
`run_rounds` call, made by `run.first_call` as every run makes them,
then the plain reference over the same rounds, and with `--control 1`
the control (the reference in three bf16 passes, `precision="high"`) in
the program's place. One JSON line per seed: the program's four
numbers against the reference and the control's.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if __name__ == "__main__":
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


def readings(spec, cell, seed: int, control: bool) -> dict:
    from bench import compare, run, workload

    limits = spec.limits(cell)
    t0 = time.time()
    cfg, data, problem, caller, res = run.first_call(spec, cell, seed)
    t1 = time.time()
    prog = workload.host_outputs(cfg, res)
    mesh = problem.mesh
    workload.free(res.state, problem.state0, problem.batch)
    del res, problem, caller
    gc.collect()
    ref = workload.reference_outputs(cfg, data, seed, prog["rounds_run"],
                                     mesh=mesh)
    t2 = time.time()
    out = {"seed": seed, "cell": cell["name"],
           "rounds": prog["rounds_run"], "stopped": prog["stopped_early"],
           "setup_and_call_s": t1 - t0, "reference_s": t2 - t1,
           "program": compare.numbers(prog, ref, limits["grad_floor"])}
    if control:
        ctl = workload.reference_outputs(cfg, data, seed, prog["rounds_run"],
                                         "high", mesh=mesh)
        out["control"] = compare.numbers(ctl, ref, limits["grad_floor"])
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import device, run

    run.compile_cache()
    spec = run.Spec()
    cell = spec.cell(args.workload)
    device.require_tpu(cell["chips"])
    for s in args.seeds.split(","):
        print(json.dumps(readings(spec, cell, int(s), bool(args.control))),
              flush=True)


if __name__ == "__main__":
    main()
