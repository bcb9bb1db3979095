"""Async (stale-x̄) round-engine benchmark: CR / objective vs staleness.

Sweeps `max_staleness` under a heterogeneous periodic arrival process
(client i communicates every p_i rounds — the deterministic straggler
scenario) and reports, per algorithm, the communication rounds to the
paper's stopping rule, the final objective and the staleness actually
used. The interesting read-out is the DEGRADATION CURVE: how much extra
CR a bounded-staleness x̄ costs relative to the synchronous masked run
(max_staleness=0, which is bitwise the synchronous engine).

Second part (subprocess, 8 fake CPU devices): lowers the sharded async
round to HLO and asserts it issues exactly as many MODEL-SIZE all-reduces
as the synchronous masked round — the staleness buffer is per-client
state riding next to z_i, so eq. (11) stays the round's one psum and
overlapping costs no extra communication.
"""
from __future__ import annotations

import subprocess
import sys
import textwrap

import jax
import numpy as np

from benchmarks.common import M_CLIENTS, fake_cpu_env, make_problem
from repro.config import FedConfig
from repro.core import make_algorithm, run_rounds
from repro.core.selection import AvailabilityParticipation

STALENESS = [0, 1, 2, 4]
K0 = 10
MAX_ROUNDS = 500
ALGOS = {
    "fedgia_d": dict(algorithm="fedgia", sigma_t=0.15, h_policy="diag_ema",
                     alpha=1.0),  # branch split = the arrival mask
    "scaffold": dict(algorithm="scaffold", lr=0.01),
}

_SHARDED_SCRIPT = textwrap.dedent(
    """
    import re
    import jax, jax.numpy as jnp, numpy as np
    from repro.config import FedConfig
    from repro.core import api, engine, make_algorithm
    from repro.data import linreg_noniid
    from repro.launch.mesh import make_host_mesh
    from repro.models import LeastSquares

    m, n, d = 8, 24, 320
    batch = {k: jnp.asarray(v) for k, v in linreg_noniid(0, d, n, m).items()}
    model = LeastSquares(n)
    mesh = make_host_mesh(data=8)
    fed = FedConfig(algorithm="fedgia", num_clients=m, k0=5, alpha=1.0,
                    sigma_t=0.3, h_policy="diag_ema")
    algo = make_algorithm(fed, model.loss, model=model)
    s0 = algo.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1),
                   init_batch=batch)

    def model_size_all_reduces(stale):
        rf = engine.make_round_fn(algo, mesh, masked=True, stale=stale)
        st, b = engine.shard_inputs(algo, s0, batch, mesh)
        args = (st, b, jnp.ones((m,), bool))
        if stale:
            args = args + (api.init_stale_xbar(s0["x"], m, 2),)
        txt = jax.jit(rf).lower(*args).compile().as_text()
        shapes = re.findall(r"= (\\S+) all-reduce\\(", txt)
        return sum(1 for s in shapes if re.search(r"\\[\\d", s))

    sync, asyn = model_size_all_reduces(False), model_size_all_reduces(True)
    assert asyn == sync, (
        f"async round changed the model-size all-reduce count: "
        f"{sync} -> {asyn}")
    print(f"ASYNC_SHARDED_OK model_size_all_reduces={asyn}")
    """
)


def _arrival(m: int, horizon: int) -> AvailabilityParticipation:
    # heterogeneous speeds 1..4 rounds, deterministic (variance-free sweep)
    return AvailabilityParticipation.from_periods(
        m, 1 + (np.arange(m) % 4), horizon=horizon
    )


def run():
    rows = []
    model, batch, tol = make_problem("linreg", 0)
    for algo_key, hp in ALGOS.items():
        fed = FedConfig(num_clients=M_CLIENTS, k0=K0, **hp)
        algo = make_algorithm(fed, model.loss, model=model)
        state = algo.init(model.init(jax.random.PRNGKey(0)),
                          jax.random.PRNGKey(1), init_batch=batch)
        pol = _arrival(M_CLIENTS, MAX_ROUNDS)
        for s in STALENESS:
            res = run_rounds(algo, state, batch, MAX_ROUNDS, tol=tol,
                             participation=pol, async_rounds=True,
                             max_staleness=s)
            rows.append({
                "algo": algo_key,
                "max_staleness": s,
                "staleness_seen": int(res.history["staleness_max"].max()),
                "cr": 2 * res.rounds_run,
                "time_s": res.wall_s,
                "obj": float(res.history["f_xbar"][-1]),
                "converged": res.stopped_early,
            })
    return rows


def run_sharded() -> str:
    env = fake_cpu_env(8)
    out = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "ASYNC_SHARDED_OK" in out.stdout, out.stdout + out.stderr
    return out.stdout


def main():
    rows = run()
    print("algo,max_staleness,staleness_seen,CR,time_s,obj,converged")
    for r in rows:
        print(f"{r['algo']},{r['max_staleness']},{r['staleness_seen']},"
              f"{r['cr']},{r['time_s']:.3f},{r['obj']:.6f},{r['converged']}")
    # bounded staleness must stay bounded, and the s=0 column is the
    # synchronous reference the degradation is measured against
    for r in rows:
        assert r["staleness_seen"] <= r["max_staleness"], r
    crs = [r["cr"] for r in rows if r["algo"] == "fedgia_d" and r["converged"]]
    if len(crs) >= 2:
        assert max(crs) <= 5 * min(crs), (
            f"staleness blew up FedGiA CR beyond the expected band: {crs}")
    print("\n-- sharded async path (8 fake devices) --")
    print(run_sharded())
    return rows


if __name__ == "__main__":
    main()
