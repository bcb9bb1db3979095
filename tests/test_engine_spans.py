"""`run_rounds`' host spans, read back from a profiler trace on the CPU.

`run_rounds` records a span of its name around each call and, inside it, the
phases `run_rounds.prepare`, `.lower`, `.compile` (one of each per
AOT-compiled chunk length) and `.fetch` as `jax.profiler.TraceAnnotation`s.
These tests trace real calls into a temporary directory and read the
xplane with `jax.profiler.ProfileData` alone."""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.config import FedConfig
from repro.core import make_algorithm, make_policy, run_rounds
from repro.data import linreg_noniid
from repro.models import LeastSquares

M, N, D = 8, 20, 400
CALL = "run_rounds"
PHASES = ("run_rounds.prepare", "run_rounds.lower", "run_rounds.compile",
          "run_rounds.fetch")


@pytest.fixture(scope="module")
def problem():
    batch = {k: jnp.asarray(v) for k, v in linreg_noniid(0, D, N, M).items()}
    model = LeastSquares(N)
    fed = FedConfig(algorithm="fedgia", num_clients=M, k0=3, sigma_t=0.2,
                    h_policy="diag_ema", alpha=0.5)
    algo = make_algorithm(fed, model.loss, model=model)
    state = algo.init(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1), init_batch=batch)
    return algo, state, batch


def _traced(tmp_path, fn):
    """Run `fn` under the profiler; the host events whose name starts
    with `run_rounds`, as (name, start_ns, end_ns) by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, int(e.start_ns), int(e.end_ns))
                           for e in line.events
                           if e.name.startswith(CALL)]
    return sorted(events, key=lambda e: e[1])


def _by_call(events):
    """Each `run_rounds` span with the phase spans that lie inside it;
    asserts that every phase span lies inside exactly one call."""
    calls = [(s, e, []) for n, s, e in events if n == CALL]
    for n, s, e in events:
        if n == CALL:
            continue
        owners = [c for c in calls if c[0] <= s and e <= c[1]]
        assert len(owners) == 1, f"{n} [{s}, {e}) lies in {len(owners)} calls"
        owners[0][2].append((n, s, e))
    return [phases for _, _, phases in calls]


@pytest.mark.parametrize("rounds,chunk,tol,aot_lengths", [
    (40, 32, 1e-3, 1),     # tol on: one length, the remainder lazy
    (10, 4, 0.0, 2),       # tol off: a full chunk length and a remainder
    (20, "auto", 0.0, 2),  # auto: candidates 8 and 12 (clipped)
], ids=["tol", "remainder", "auto"])
def test_each_call_has_its_phases_in_order(tmp_path, problem, rounds, chunk,
                                           tol, aot_lengths):
    algo, state, batch = problem

    def two_calls():
        for _ in range(2):
            res = run_rounds(algo, state, batch, rounds, tol=tol,
                             chunk_size=chunk)
            jax.block_until_ready(res.state)

    events = _traced(tmp_path, two_calls)
    assert {n for n, _, _ in events} == {CALL, *PHASES}
    calls = _by_call(events)
    assert len(calls) == 2
    for phases in calls:
        names = [n for n, _, _ in phases]
        assert names == (["run_rounds.prepare"]
                         + ["run_rounds.lower", "run_rounds.compile"]
                         * aot_lengths + ["run_rounds.fetch"])
        # the phases do not overlap: the chunk loop lies between them
        for (_, _, e0), (_, s1, _) in zip(phases, phases[1:]):
            assert e0 <= s1


def test_a_call_that_raises_leaves_no_span_open(tmp_path, problem):
    algo, state, batch = problem

    def raise_then_call():
        with pytest.raises(ValueError, match="chunk_size"):
            run_rounds(algo, state, batch, 8, chunk_size="bogus")
        res = run_rounds(algo, state, batch, 8, chunk_size=4)
        jax.block_until_ready(res.state)

    calls = _by_call(_traced(tmp_path, raise_then_call))
    assert [[n for n, _, _ in c] for c in calls] == [
        ["run_rounds.prepare"],
        ["run_rounds.prepare", "run_rounds.lower", "run_rounds.compile",
         "run_rounds.fetch"]]


@pytest.mark.parametrize("path", ["legacy", "offload"])
def test_host_driven_loops_close_prepare_before_the_rounds(tmp_path, problem,
                                                           path):
    algo, state, batch = problem
    kw = ({"scan": False} if path == "legacy" else
          {"store": "offload",
           "participation": make_policy("uniform", M, 0.5, seed=3)})

    def one_call():
        res = run_rounds(algo, state, batch, 6, **kw)
        jax.block_until_ready(res.state)

    events = _traced(tmp_path, one_call)
    (phases,) = _by_call(events)
    assert [n for n, _, _ in phases] == ["run_rounds.prepare"]
    (_, _, call_end), = [e for e in events if e[0] == CALL]
    # the loop's own compile and rounds come after the preparation
    assert phases[0][2] < call_end
