"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of `workloads` in BENCHMARK.json) names a
configuration file and a traffic mix (`bench/traffic/<traffic>.json`);
its comparison limits are in `bench/limits/<cell>.json` and each
per-layer metric is read by `bench/metrics/<metric>.py`. The run builds
the problem from the seed, makes one `run_rounds` call exactly as the
window will (that call is the one checked against the plain reference),
then times whole calls for `--seconds` seconds; with `--trace 1` it
traces `trace_calls` calls instead and reports the per-layer metrics.
A cell on several chips lays its clients by rows over a `data` mesh of
them (`bench/workload.py`), and its reference runs over the same mesh.
The last line of stdout is one JSON object; the numbers compared, each
with its limit, are the last lines of stderr and the last key of that
object. A run that finds no TPU exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if __name__ == "__main__":
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    # the persistent compilation cache lives in this checkout, at a fixed
    # path: only a cell's first run here compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")


class Spec:
    """BENCHMARK.json and the files it names, found by name."""

    def __init__(self, root: str = ROOT, bench_dir: str = HERE):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        self.root = root
        self.dir = bench_dir

    def _json(self, *parts: str) -> dict:
        with open(os.path.join(*parts)) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")

    def config(self, cell: dict) -> dict:
        entry = next(c for c in self.bench["configs"]
                     if c["name"] == cell["config"])
        return self._json(self.root, entry["file"])

    def traffic(self, cell: dict) -> dict:
        return self._json(self.dir, "traffic", cell["traffic"] + ".json")

    def limits(self, cell: dict) -> dict:
        return self._json(self.dir, "limits", cell["name"] + ".json")

    def metrics(self, kind: str, cell: dict) -> list:
        return [m for m in self.bench[kind]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, name: str):
        """`bench/metrics/<name>.py`, or where that is absent the reader
        of the name's stem (`device_idle_share.py` reads
        `device_idle_share.rounds` and `device_idle_share.solve`)."""
        path = os.path.join(self.dir, "metrics", name + ".py")
        if not os.path.exists(path):
            path = os.path.join(self.dir, "metrics",
                                name.split(".")[0] + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod


class CompileCounter:
    """Programs compiled, and programs loaded from the persistent cache,
    in this process: XLA compile events less cache hits, and the hits."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        from jax import monitoring

        self.compiles = self.hits = 0

        def on_duration(event, duration, **kw):
            if event == self.COMPILE:
                self.compiles += 1

        def on_event(event, **kw):
            if event == self.HIT:
                self.hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.compiles - self.hits, self.hits


def window(caller, state, *, seconds=None, calls=None, span=None):
    """Whole `run_rounds` calls until `seconds` have passed or `calls`
    were made. Returns (seconds, calls, rounds, failed)."""
    n = rounds = failed = 0
    t0 = time.perf_counter()
    while True:
        if span is not None:
            with span():
                res = caller.call(state)
        else:
            res = caller.call(state)
        n += 1
        rounds += res.rounds_run
        failed += 0 if caller.solved(res) else 1
        if caller.chains:
            state = res.state
        del res
        elapsed = time.perf_counter() - t0
        if (calls is not None and n >= calls) or \
                (seconds is not None and elapsed >= seconds):
            return elapsed, n, rounds, failed


def first_call(spec: Spec, cell: dict, seed: int) -> tuple:
    """The cell's problem built from the seed, and its first call made as
    the window makes them: (cfg, data, problem, caller, result)."""
    from bench import workload

    cfg = spec.config(cell)
    data = workload.make_data(cfg, seed)
    problem = workload.build(cfg, data, seed, workload.layout(cell["chips"]))
    caller = workload.Caller(problem, spec.traffic(cell))
    return cfg, data, problem, caller, caller.call(problem.state0)


def run_cell(spec: Spec, cell: dict, seed: int, seconds: float, trace: bool,
             devs: list, peaks: dict, t_start: float,
             peak_fn=None) -> dict:
    """One run of `cell` on `devs`; returns the result object.
    `peak_fn(devs)` reads the peak device bytes (default: the devices'
    own `memory_stats`)."""
    import jax

    from bench import compare, counting, device, workload
    from bench import trace as tr

    counter = CompileCounter()
    traffic, limits = spec.traffic(cell), spec.limits(cell)
    # set-up makes the first call, made as the window makes them, and
    # where the window chains its calls a second one from the first's
    # state: a call from a chained state may compile programs of its own
    cfg, data, problem, caller, first = first_call(spec, cell, seed)
    prog = workload.host_outputs(cfg, first)
    if caller.chains:
        workload.free(problem.state0)
        warm = caller.call(first.state)
        workload.free(first.state)
        first = warm
        del warm
    setup_s = time.time() - t_start
    # the window owns the state it chains: no reference to it stays here
    start = [first.state if caller.chains else problem.state0]
    del first
    c0, h0 = counter.snapshot()

    e2e, per_layer, extra = {}, {}, {}
    if not trace:
        secs, calls, rounds, failed = window(caller, start.pop(),
                                             seconds=seconds)
        e2e = {"rounds_per_s": rounds / secs, "setup_s": setup_s}
        if traffic["mode"] == "solve":
            e2e["solve_s"] = secs / calls
    else:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        # no Python tracer: it would slow the host work the trace measures;
        # the host's own events still label the device's idle gaps
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        try:
            jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                secs, calls, rounds, failed = window(
                    caller, start.pop(), calls=traffic["trace_calls"],
                    span=lambda: jax.profiler.TraceAnnotation(tr.SPAN))
            finally:
                jax.profiler.stop_trace()
            trace_data = tr.read_xplane(tr.find_xplane(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        reading = tr.Reading(trace_data, chips=len(devs), rounds=rounds,
                             calls=calls, peaks=peaks,
                             kernels=cfg.get("kernels", {}),
                             collectives=cfg.get("collectives"),
                             **counting.for_config(cfg))
        readers = {m["name"]: spec.reader(m["name"])
                   for m in spec.metrics("per_layer", cell)}
        per_layer = tr.collect(reading, readers)
        extra = {"busy_s": reading.busy_s, "window_s": reading.window_s,
                 "breakdown": reading.breakdown()}
    c1, h1 = counter.snapshot()
    peak = (peak_fn or device.peak_bytes)(devs)
    e2e["peak_hbm_bytes"] = peak
    mesh = problem.mesh
    workload.free(problem.batch, problem.state0)
    del problem, caller
    gc.collect()

    ref = workload.reference_outputs(cfg, data, seed, prog["rounds_run"],
                                     mesh=mesh)
    nums = compare.numbers(prog, ref, limits["grad_floor"])
    correct = compare.verdict(nums, limits)

    metrics = {}
    kind, values = ("per_layer", per_layer) if trace else ("end_to_end", e2e)
    for m in spec.metrics(kind, cell):
        if m["name"] in values:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    dev = device.describe(devs)
    dev["memory_peak_bytes"] = peak
    result = {"correct": bool(correct), "attempted": calls,
              "failed": failed, "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = extra["busy_s"]
        dev["window_s"] = extra["window_s"]
        result["breakdown"] = extra["breakdown"]
    result["window"] = {"seconds": secs, "calls": calls, "rounds": rounds,
                        "compiles": c1 - c0, "cache_loads": h1 - h0,
                        "setup_s": setup_s,
                        "checked_call_rounds": prog["rounds_run"]}
    result["checks"] = compare.report(nums, limits)
    return result


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def compile_cache() -> None:
    """JAX's persistent compilation cache in this checkout (the directory
    set above), every program in it, and no eviction: an evicting cache
    keeps an access-time file beside each entry, and one entry found
    without it makes every later write fail, so that each run compiles
    anew."""
    import jax

    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> None:
    args = parse(argv)
    spec = Spec()
    cell = spec.cell(args.workload)
    from bench import device

    compile_cache()
    devs, peaks = device.require_tpu(cell["chips"])
    emit(run_cell(spec, cell, args.seed, args.seconds, bool(args.trace),
                  devs, peaks, T_START))


if __name__ == "__main__":
    main()
