"""Round-engine benchmark: scan-compiled chunks vs the seed's per-round
dispatch loop — plus the client-sharded and async (stale-x̄) engine paths,
and the flat-buffer round path against its per-leaf pytree twin — on the
paper's linreg problem, fixed round count (no early stop) so every path
executes comparable math.

The legacy path pays one dispatch + one metric host-sync per round; the
scan path pays one dispatch per chunk and no per-round syncs. On CPU with
the paper-scale problem the speedup is dominated by removed dispatch
latency — exactly the overhead that grows with round count. The sharded
path runs in a subprocess over 8 FAKE CPU devices (so its round/s is a
plumbing sanity number, not a hardware claim); `scan_overlap` is the
same sharded run with `overlap="scatter"` (eq. (11) split into an early
reduce-scatter plus a deferred consensus all-gather), so its row pins
that the carry-slot bookkeeping costs ~nothing on one socket; the async
path adds the staleness carry + per-client anchor selects to the scan
path, and its round/s shows that overlap bookkeeping is (near) free.

`scan` is the shipping configuration (flat=True: ravel-once (m, N) client
state, contiguous eq.-11 reduction, fused branch update);
`scan_pytree` is the same scan engine with `flat=False`. The two are
measured INTERLEAVED (scan/pytree/scan/pytree/...) so slow drift on a
shared CI runner hits both paths equally; the reported ratio is the
ratio of the two per-path medians. On the single-leaf linreg model the flat win is
moderate (ravel is a no-op reshape, the gain is fewer HLO ops per round);
multi-leaf models widen it.

`active_1m` is the active-set store at the regime the dense store cannot
represent: m = 10^6 clients, alpha = 10^-4 (100 participants per round,
FedAvg — the frozen-client family the store accelerates). The round's
trajectories and gradient evaluations are (100, N) tiles instead of
(10^6, N) buffers; what remains O(m) per round is the mask draw and the
one streaming eq.-11 reduction (scattered back to the dense layout so
results stay bitwise the dense store's — api.flat_round_aggregate_active).
The batch is built directly (one sample per client) because the paper's
heterogeneous-size splitter is O(m^2) at this scale.

`offload_1m` is the host-offloaded store at the same scale, on the
algorithm the offload exists for: FedPD carries a RESIDENT (m, N) dual
buffer, so at m = 10^6 the client state alone is ~0.5 GB — dense OR
active, that buffer lives on the device; `store="offload"` moves it (and
the resident batch) to pinned host memory and shuttles (100, N) tiles
per round. With `aggregate="packed"` the eq.-11 reduction sums the tile
directly, so NOTHING O(m) is resident on the device — the row reports
the compiled tile round's peak device bytes (XLA memory_analysis, None
where the backend doesn't report it) next to the analytic dense-store
footprint it displaced.

`run()` returns the machine-readable dict that `benchmarks/run.py` dumps
to BENCH_engine.json (round/s per path). Env knobs for CI budgets:
ENGINE_BENCH_ROUNDS (default 200), ENGINE_BENCH_REPEATS (default 3),
ENGINE_BENCH_1M_ROUNDS (default 3), ENGINE_BENCH_1M_CLIENTS (default
1_000_000 — shrink for smoke runs).
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import textwrap

import jax
import numpy as np

from benchmarks.common import (M_CLIENTS, fake_cpu_env, make_problem,
                               million_client_batch)
from repro.config import FedConfig
from repro.core import make_algorithm, run_rounds
from repro.core.selection import AvailabilityParticipation

ROUNDS = int(os.environ.get("ENGINE_BENCH_ROUNDS", "200"))
REPEATS = int(os.environ.get("ENGINE_BENCH_REPEATS", "3"))
ROUNDS_1M = int(os.environ.get("ENGINE_BENCH_1M_ROUNDS", "3"))
M_1M = int(os.environ.get("ENGINE_BENCH_1M_CLIENTS", "1000000"))
ALPHA_1M = 1e-4

_SHARDED_SCRIPT = textwrap.dedent(
    """
    import jax, jax.numpy as jnp, numpy as np
    from benchmarks.common import M_CLIENTS, make_problem
    from repro.config import FedConfig
    from repro.core import make_algorithm, run_rounds
    from repro.launch.mesh import make_host_mesh

    ROUNDS = {rounds}
    model, batch, _ = make_problem("linreg", 0)
    fed = FedConfig(algorithm="fedgia", num_clients=M_CLIENTS, k0=5,
                    alpha=0.5, sigma_t=0.15, h_policy="diag_ema")
    algo = make_algorithm(fed, model.loss, model=model)
    state = algo.init(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1), init_batch=batch)
    mesh = make_host_mesh(data=8)
    res = run_rounds(algo, state, batch, ROUNDS, scan=True, mesh=mesh,
                     overlap="{overlap}")
    print(f"SHARDED_WALL_S={{res.wall_s:.6f}}")
    """
)


def _measure(fn):
    walls = []
    for _ in range(REPEATS):
        walls.append(fn().wall_s)
    return float(np.median(walls))


def run():
    model, batch, _ = make_problem("linreg", 0)
    fed = FedConfig(algorithm="fedgia", num_clients=M_CLIENTS, k0=5,
                    alpha=0.5, sigma_t=0.15, h_policy="diag_ema")
    algo = make_algorithm(fed, model.loss, model=model)
    state = algo.init(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1), init_batch=batch)

    res_loop = res_scan = res_pytree = res_async = None

    def loop():
        nonlocal res_loop
        res_loop = run_rounds(algo, state, batch, ROUNDS, scan=False)
        return res_loop

    def scan():
        nonlocal res_scan
        res_scan = run_rounds(algo, state, batch, ROUNDS, scan=True)
        return res_scan

    def scan_pytree():
        nonlocal res_pytree
        res_pytree = run_rounds(algo, state, batch, ROUNDS, scan=True,
                                flat=False)
        return res_pytree

    # async: heterogeneous periodic arrivals, bounded staleness 2. alpha is
    # irrelevant (the arrival mask IS the branch split).
    pol = AvailabilityParticipation.from_periods(
        M_CLIENTS, 1 + (np.arange(M_CLIENTS) % 4), horizon=ROUNDS)

    def asyn():
        nonlocal res_async
        res_async = run_rounds(algo, state, batch, ROUNDS, scan=True,
                               participation=pol, async_rounds=True,
                               max_staleness=2)
        return res_async

    loop_s, async_s = _measure(loop), _measure(asyn)
    # flat vs pytree scan: interleaved repeats so runner drift hits both
    # paths equally; per-path median
    flat_walls, pytree_walls = [], []
    for _ in range(REPEATS):
        flat_walls.append(scan().wall_s)
        pytree_walls.append(scan_pytree().wall_s)
    scan_s = float(np.median(flat_walls))
    pytree_s = float(np.median(pytree_walls))
    # the sync paths must agree before their times are comparable (flat is
    # bitwise the pytree scan on a single device — tests/test_flat.py)
    for k in ("f_xbar", "grad_sq_norm"):
        np.testing.assert_allclose(res_scan.history[k], res_loop.history[k],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(res_scan.history[k],
                                      res_pytree.history[k])
    assert int(res_async.history["staleness_max"].max()) <= 2

    sharded_s = run_sharded()
    sharded_overlap_s = run_sharded(overlap="scatter")
    active_1m = run_active_1m()
    offload_1m = run_offload_1m()
    r = {
        "rounds": ROUNDS,
        "clients": M_CLIENTS,
        "paths": {
            "legacy": {"wall_s": loop_s, "rounds_per_s": ROUNDS / loop_s},
            "scan": {"wall_s": scan_s, "rounds_per_s": ROUNDS / scan_s,
                     "note": "flat-buffer rounds (the default path)"},
            "scan_pytree": {"wall_s": pytree_s,
                            "rounds_per_s": ROUNDS / pytree_s,
                            "note": "per-leaf pytree rounds (--no-flat)"},
            "sharded": {"wall_s": sharded_s,
                        "rounds_per_s": ROUNDS / sharded_s,
                        "note": "8 fake CPU devices, one physical socket"},
            # overlap on 8 FAKE devices shares one socket, so round/s is a
            # no-extra-overhead sanity number; the latency win is priced by
            # the wall-clock bench's byte clock (min(compute, comm) credit)
            "scan_overlap": {"wall_s": sharded_overlap_s,
                             "rounds_per_s": ROUNDS / sharded_overlap_s,
                             "note": "sharded scan, overlap='scatter' "
                                     "(early RS + deferred consensus AG)"},
            "async": {"wall_s": async_s, "rounds_per_s": ROUNDS / async_s,
                      "max_staleness": 2},
            "active_1m": active_1m,
            "offload_1m": offload_1m,
        },
        "speedup_scan_vs_legacy": loop_s / scan_s,
        "speedup_flat_vs_pytree": pytree_s / scan_s,
        # NOTE: not a pure bookkeeping-overhead ratio — stale rounds
        # evaluate gradients at PER-CLIENT anchors (a batched dot), which
        # CPU XLA parallelizes differently from the sync path's
        # shared-params evaluation; on CPU the async path is routinely
        # FASTER. The staleness carry itself adds only elementwise selects.
        "overhead_async_vs_scan": async_s / scan_s,
    }
    return r


def run_active_1m() -> dict:
    """Million-client active-store rounds: FedAvg, m=M_1M, alpha=1e-4.

    Uses the `LeastSquares` model on a directly-built one-sample-per-
    client batch (n=32 features; the resident batch is the only (m, ...)
    input). Dense has no twin row here — its per-round working set would
    be k0 (m, N) trajectory buffers plus m gradient evaluations."""
    from repro.core import make_policy
    from repro.models import LeastSquares

    n = 32
    batch = million_client_batch(M_1M, n)
    model = LeastSquares(n)
    fed = FedConfig(algorithm="fedavg", num_clients=M_1M, k0=5, lr=0.01)
    algo = make_algorithm(fed, model.loss, model=model)
    state = algo.init(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1), init_batch=batch)
    pol = make_policy("uniform", M_1M, ALPHA_1M, seed=0)
    res = run_rounds(algo, state, batch, ROUNDS_1M, participation=pol,
                     store="active")
    assert res.rounds_run == ROUNDS_1M
    assert int(res.history["selected"][0]) == pol.n_selected
    return {
        "wall_s": res.wall_s,
        "rounds_per_s": ROUNDS_1M / res.wall_s,
        "clients": M_1M,
        "alpha": ALPHA_1M,
        "participants_per_round": pol.n_selected,
        "rounds": ROUNDS_1M,
        "note": "active-set store, FedAvg: (|C|, N) tile rounds at m=1e6",
    }


def run_offload_1m() -> dict:
    """Million-client host-offloaded rounds: FedPD, m=M_1M, alpha=1e-4,
    store="offload" + aggregate="packed".

    FedPD is the demonstration because its dual variable λᵢ is a
    resident (m, N) client buffer — the thing the offload store exists
    to move off the device. The row carries the measured device/host
    split next to the analytic dense footprint it displaced."""
    from repro.core import make_policy
    from repro.models import LeastSquares

    n = 32
    batch = million_client_batch(M_1M, n)
    model = LeastSquares(n)
    fed = FedConfig(algorithm="fedpd", num_clients=M_1M, k0=5, lr=0.05,
                    fedpd_eta=1.0)
    algo = make_algorithm(fed, model.loss, model=model)
    state = algo.init(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1), init_batch=batch)
    pol = make_policy("uniform", M_1M, ALPHA_1M, seed=0)
    res = run_rounds(algo, state, batch, ROUNDS_1M, participation=pol,
                     store="offload", aggregate="packed")
    assert res.rounds_run == ROUNDS_1M
    assert int(res.history["selected"][0]) == pol.n_selected
    # the dense (or active) store would keep λ resident ON DEVICE: one
    # (m, N) flat buffer (N = lane-padded model size)
    from repro.utils import pytree as pt
    spec = pt.ravel_spec(state["x"])
    dense_resident = M_1M * spec.padded_size * np.dtype(spec.dtype).itemsize
    peak = res.extras.get("device_peak_bytes")
    # the fixed per-round overhead (mask, ids, metric stack) only
    # amortizes at real scale — skip the footprint assert on shrunk
    # ENGINE_BENCH_1M_CLIENTS smoke runs
    if peak is not None and M_1M >= 100_000:
        assert peak < dense_resident, (
            f"offload tile round peaks at {peak}B on device — not below "
            f"the {dense_resident}B dense-store λ buffer it displaced")
    return {
        "wall_s": res.wall_s,
        "rounds_per_s": ROUNDS_1M / res.wall_s,
        "clients": M_1M,
        "alpha": ALPHA_1M,
        "participants_per_round": pol.n_selected,
        "rounds": ROUNDS_1M,
        "peak_device_bytes": peak,
        "host_resident_bytes": res.extras.get("host_resident_bytes"),
        "dense_resident_bytes": dense_resident,
        "note": "host-offloaded store + packed eq. (11), FedPD: resident "
                "(m, N) duals in host memory, (|C|, N) tiles on device",
    }


def run_sharded(overlap: str = "off") -> float:
    env = fake_cpu_env(8)
    out = subprocess.run(
        [sys.executable, "-c",
         _SHARDED_SCRIPT.format(rounds=ROUNDS, overlap=overlap)],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    m = re.search(r"SHARDED_WALL_S=([\d.]+)", out.stdout)
    assert m, out.stdout + out.stderr
    return float(m.group(1))


def main():
    r = run()
    print("path,wall_s,rounds_per_s")
    for name, p in r["paths"].items():
        print(f"{name},{p['wall_s']:.3f},{p['rounds_per_s']:.1f}")
    print(f"speedup scan vs legacy: {r['speedup_scan_vs_legacy']:.2f}x, "
          f"flat vs pytree: {r['speedup_flat_vs_pytree']:.2f}x, "
          f"async overhead vs scan: {r['overhead_async_vs_scan']:.2f}x")
    assert r["speedup_scan_vs_legacy"] > 1.0, (
        f"scan engine slower than per-round dispatch: {r}")
    # interleaved medians: the flat round path must not lose to its pytree
    # twin (2% grace for shared-runner noise; the check_bench gate pins
    # the absolute round/s trajectory)
    assert r["speedup_flat_vs_pytree"] >= 0.98, (
        f"flat rounds slower than pytree rounds: {r}")
    return r


if __name__ == "__main__":
    main()
