"""Shared benchmark harness: runs a federated algorithm to the paper's
stopping rule (eq. 35) and reports Obj / CR / wall time like Table IV.

All runs go through the scan-compiled round engine (core/engine.py) with
the stopping rule evaluated on device; wall times exclude compilation
(the engine pre-compiles its chunks, matching the old warm-up convention).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import FedConfig
from repro.core import make_algorithm, run_rounds
from repro.data import linreg_noniid, logreg_data
from repro.models import LeastSquares, LogisticRegression, NonConvexLogistic

# CPU-budget problem sizes (paper: m=128, n in {100, 1024, 200}, d up to 2e5)
M_CLIENTS = 64
N_DIM = 100
D_SAMPLES = 6400
MAX_ROUNDS = 500


def fake_cpu_env(num_devices: int = 8) -> dict:
    """Environment for a child process that runs on `num_devices` fake CPU
    devices. The child is held to the CPU backend: a parent that has
    imported JAX may hold the accelerator, and a second process that
    opens it fails or hangs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={num_devices}"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    return env


def make_problem(name: str, seed: int):
    if name == "linreg":
        model = LeastSquares(N_DIM)
        raw = linreg_noniid(seed, D_SAMPLES, N_DIM, M_CLIENTS)
        tol = 1e-7
    elif name == "logreg":
        model = LogisticRegression(N_DIM)
        raw = logreg_data(seed, D_SAMPLES, N_DIM, M_CLIENTS)
        tol = (5.0 / D_SAMPLES) * 1e-6
    elif name == "ncvx_logreg":
        model = NonConvexLogistic(N_DIM)
        raw = logreg_data(seed, D_SAMPLES, N_DIM, M_CLIENTS)
        tol = (5.0 / D_SAMPLES) * 1e-6
    else:
        raise KeyError(name)
    batch = {k: jnp.asarray(v) for k, v in raw.items()}
    return model, batch, tol


def million_client_batch(m: int, n: int = 32, seed: int = 0) -> dict:
    """One least-squares sample per client, built directly: the paper's
    heterogeneous-size splitter (`linreg_noniid`) is O(m^2), which rules
    it out at m = 10^6. The batch is the only (m, ...) input."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m, 1, n)).astype(np.float32)
    x_star = rng.standard_normal(n).astype(np.float32)
    b = (A @ x_star + 0.1 * rng.standard_normal((m, 1))).astype(np.float32)
    return {"A": jnp.asarray(A), "b": jnp.asarray(b),
            "mask": jnp.ones((m, 1), jnp.float32)}


ALGO_HPARAMS = {
    # paper §V.D settings adapted to the synthetic stand-in data
    "fedavg": dict(lr=0.01),
    "fedprox": dict(lr=0.002, prox_mu=1e-4, inner_steps=5),
    "fedpd": dict(lr=0.05, fedpd_eta=1.0, inner_steps=5),
    "scaffold": dict(lr=0.01),
    "fedgia_d": dict(sigma_t=0.15, h_policy="diag_ema", alpha=0.5),
    "fedgia_g": dict(sigma_t=0.15, h_policy="gram", alpha=0.5, collapsed=False),
    "fedgia": dict(sigma_t=0.15, h_policy="scalar", alpha=0.5),
}


def run_algorithm(algo_key: str, problem: str, k0: int, seed: int = 0,
                  max_rounds: int = MAX_ROUNDS, collect_history: bool = False,
                  scan: bool = True):
    model, batch, tol = make_problem(problem, seed)
    hp = dict(ALGO_HPARAMS[algo_key])
    name = "fedgia" if algo_key.startswith("fedgia") else algo_key
    alpha = hp.pop("alpha", 1.0)  # baselines: full participation (paper §V.D)
    fed = FedConfig(algorithm=name, num_clients=M_CLIENTS, k0=k0, alpha=alpha,
                    **hp)
    algo = make_algorithm(fed, model.loss, model=model)
    state = algo.init(model.init(jax.random.PRNGKey(seed)),
                      jax.random.PRNGKey(seed + 1), init_batch=batch)
    res = run_rounds(algo, state, batch, max_rounds, tol=tol, scan=scan)
    hist = (
        list(zip(res.history["f_xbar"].tolist(),
                 res.history["grad_sq_norm"].tolist()))
        if collect_history else []
    )
    return {
        "algo": algo_key,
        "problem": problem,
        "k0": k0,
        "obj": float(res.history["f_xbar"][-1]),
        "err": float(res.history["grad_sq_norm"][-1]),
        "rounds": res.rounds_run,
        "cr": 2 * res.rounds_run,
        "time_s": res.wall_s,
        "converged": res.stopped_early,
        "history": hist,
    }
