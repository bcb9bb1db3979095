"""Smoke run of FedGiA's round engine on a TPU, in one process.

    python chip_smoke.py             # phases (a)-(c) on one chip
    python chip_smoke.py --chips 4   # the client-sharded round on a 4-chip host

One chip:
  (a) The paper problems (linreg m=128 n=100, logreg m=128 n=1024) with
      the diagonal-H FedGiA round, through the training CLI
      (`launch.train.train`), to the eq. (35) tolerance. The default run
      (Pallas `fedgia_update` kernel, chosen by backend) and a
      `--kernel off` run must stop at the same round with the same final
      f, and the compiled round must hold the kernel (`tpu_custom_call`).
  (b) FedGiA at m=10^6 clients, n=32 (N=128 lanes), uniform
      participation alpha=1e-4, a few rounds through `core.run_rounds`;
      checked against the same rounds with the kernel off.
  (c) The host-offloaded store at m=10^6 (FedPD, store="offload",
      aggregate="packed"); checked against the device-resident active
      store, and the placement it used is printed.
Four chips (--chips 4): phase (a) linreg and phase (b), each on one chip
and sharded over a 4-device `data` mesh with overlap "off" and "scatter":
same stop round and f, client rows spread over all four devices, and the
round's collective budget (tests/hlo_guard.py) on the COMPILED program:
one model-size all-reduce (barrier) or one reduce-scatter and one
all-gather (scatter). A budget, or a device's bytes, that does not hold
fails the run after both phases have printed their lines.

Each phase prints one line. Any failure raises, so the script exits
non-zero with no result line; so does a process that finds no TPU. The
last line of stdout is the JSON result:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "tests"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import million_client_batch  # noqa: E402
from hlo_guard import (assert_barrier_round, assert_overlap_round,  # noqa: E402
                       collective_counts)
from repro.config import FedConfig  # noqa: E402
from repro.core import engine, make_algorithm, make_policy, run_rounds  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.train import (build_algorithm, build_parser,  # noqa: E402
                                train, validate_flags)
from repro.models import LeastSquares  # noqa: E402
from repro.utils import pytree as pt  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402

# (problem, n, eq. (35) tolerance); m=128 clients share d=12800 samples
PAPER = (("linreg", 100, 1e-7), ("logreg", 1024, 5.0 / 12800 * 1e-6))
PAPER_CLIENTS = 128
PAPER_SAMPLES = 12800
PAPER_MAX_ROUNDS = 1000
M_1M = 1_000_000
N_1M = 32
ALPHA_1M = 1e-4
ROUNDS_1M = 3
F_RTOL = 1e-5  # kernel vs jnp, sharded vs one chip: fp reassociation only


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def close(a, b, rtol: float = F_RTOL) -> bool:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return bool(np.all(np.abs(a - b) <= rtol * np.maximum(np.abs(b), 1e-30)))


def require_tpu(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit("chip_smoke: no TPU found (JAX's default device is "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devs)}")
    return devs


def round_program(algo, state, batch, *, mesh=None, overlap="off",
                  masked=False):
    """(lowered HLO text, compiled text) of one flat round of `algo`, laid
    out as run_rounds lays it out (sharded over `mesh`'s data axis when
    given)."""
    spec = pt.ravel_spec(state["x"])
    flat = engine.flatten_state(algo, state, spec)
    if overlap == "scatter":
        flat["ovl_shard"] = jnp.zeros((1, spec.padded_size), spec.dtype)
    if mesh is not None:
        flat, batch = engine.shard_inputs(algo, flat, batch, mesh)
    rf = engine.make_round_fn(algo, mesh, masked=masked, flat_spec=spec,
                              overlap=overlap, donate_kernel=True)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        (flat, batch))
    if masked:
        args += (jax.ShapeDtypeStruct((algo.fed.num_clients,), bool),)
    lowered = jax.jit(rf).lower(*args)
    return lowered.as_text(dialect="hlo"), lowered.compile().as_text()


def check_budget(lowered: str, compiled: str, overlap: str,
                 label: str) -> tuple:
    """The sharded round's collective budget, on the program the chip
    runs: hlo_guard's barrier contract (one model-size all-reduce for
    eq. (11), at most the |grad|^2 diagnostic's reduce-scatter besides)
    or its overlap contract (one reduce-scatter, one all-gather, no
    all-reduce) on the COMPILED text. Returns (summary, failure or None);
    the lowered counts are printed next to the compiled ones, so a
    collective the compiler rewrote shows as such."""
    summary = (f"lowered {collective_counts(lowered)} "
               f"compiled {collective_counts(compiled)}")
    try:
        (assert_barrier_round if overlap == "off"
         else assert_overlap_round)(compiled, label)
    except AssertionError as e:
        return summary, str(e)
    return summary, None


# ------------------------------------------------------ (a) paper problems
def paper_argv(problem: str, n: int, tol: float, *extra: str) -> list:
    return ["--problem", problem, "--algo", "fedgia", "--h-policy",
            "diag_ema", "--clients", str(PAPER_CLIENTS), "--dim", str(n),
            "--samples", str(PAPER_SAMPLES), "--tol", repr(tol),
            "--rounds", str(PAPER_MAX_ROUNDS), "--log-every", "1000000",
            *extra]


def paper_algo(argv: list):
    """The algorithm, state and batch `train` builds for `argv`."""
    args = build_parser().parse_args(argv)
    return build_algorithm(args, validate_flags(args))


def train_cli(argv: list) -> dict:
    return train(build_parser().parse_args(argv))


def phase_paper(problem: str, n: int, tol: float) -> None:
    t0 = time.time()
    argv = paper_argv(problem, n, tol)
    on = train_cli(argv)
    off = train_cli(argv + ["--kernel", "off"])
    check(on["rounds"] < PAPER_MAX_ROUNDS and on["final_err"] < tol,
          f"{problem}: no eq. (35) stop in {PAPER_MAX_ROUNDS} rounds ({on})")
    check(on["rounds"] == off["rounds"],
          f"{problem}: kernel stops at round {on['rounds']}, "
          f"--kernel off at {off['rounds']}")
    check(close(on["final_f"], off["final_f"]),
          f"{problem}: final f {on['final_f']!r} vs {off['final_f']!r}")
    _, txt = round_program(*paper_algo(argv))
    check("tpu_custom_call" in txt,
          f"{problem}: the compiled round holds no Pallas kernel")
    print(f"[a] {problem} m={PAPER_CLIENTS} n={n} fedgia diag_ema "
          f"tol={tol:.3g}: kernel rounds={on['rounds']} "
          f"f={on['final_f']!r} err={on['final_err']:.3e} | --kernel off "
          f"rounds={off['rounds']} f={off['final_f']!r} | tpu_custom_call "
          f"in round: yes | {time.time() - t0:.1f}s", flush=True)


# ------------------------------------------------ (b) FedGiA at m = 10^6
def million_fedgia(use_kernel=None):
    model = LeastSquares(N_1M)
    batch = million_client_batch(M_1M, N_1M)
    fed = FedConfig(algorithm="fedgia", num_clients=M_1M, k0=5,
                    alpha=ALPHA_1M, sigma_t=0.15, h_policy="diag_ema",
                    use_kernel=use_kernel)
    algo = make_algorithm(fed, model.loss, model=model)
    state = algo.init(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1), init_batch=batch)
    return algo, state, batch


def run_million(algo, state, batch, mesh=None, overlap="off"):
    pol = make_policy("uniform", M_1M, ALPHA_1M, seed=0)
    res = run_rounds(algo, state, batch, ROUNDS_1M, participation=pol,
                     mesh=mesh, overlap=overlap)
    check(res.rounds_run == ROUNDS_1M, f"m=1e6: {res.rounds_run} rounds run")
    check(bool(np.all(res.history["selected"] == pol.n_selected)),
          f"m=1e6: selected {res.history['selected']}, "
          f"policy draws {pol.n_selected}")
    check(bool(np.all(np.isfinite(res.history["f_xbar"]))),
          f"m=1e6: f_xbar {res.history['f_xbar']}")
    z = jax.tree.leaves(res.state["z"])[0]
    check(z.shape == (M_1M, N_1M) and bool(jnp.all(jnp.isfinite(z))),
          f"m=1e6: client state z {z.shape} not finite")
    return res


def phase_million() -> None:
    t0 = time.time()
    res = run_million(*million_fedgia())
    peak = device_stats("peak_bytes_in_use")[0]
    f_on, x_on = res.history["f_xbar"], res.state["x"]
    del res
    gc.collect()
    off = run_million(*million_fedgia(use_kernel=False))
    check(close(f_on, off.history["f_xbar"]),
          f"m=1e6 f_xbar: kernel {f_on} vs jnp {off.history['f_xbar']}")
    check(jax.tree.all(jax.tree.map(
        lambda a, b: close(a, b, 1e-4), x_on, off.state["x"])),
          "m=1e6: final x differs between kernel and jnp rounds")
    del off
    gc.collect()
    _, txt = round_program(*million_fedgia(), masked=True)
    check("tpu_custom_call" in txt,
          "m=1e6: the compiled round holds no Pallas kernel")
    print(f"[b] fedgia m={M_1M} n={N_1M} (N=128 lanes) uniform "
          f"alpha={ALPHA_1M:g}: {ROUNDS_1M} rounds f_xbar="
          f"{[float(v) for v in f_on]} matches --kernel off | "
          f"tpu_custom_call in round: yes | peak_bytes_in_use={peak} | "
          f"{time.time() - t0:.1f}s", flush=True)


# ------------------------------------------ (c) offload store at m = 10^6
def run_fedpd_1m(store: str):
    model = LeastSquares(N_1M)
    batch = million_client_batch(M_1M, N_1M)
    fed = FedConfig(algorithm="fedpd", num_clients=M_1M, k0=5, lr=0.05,
                    fedpd_eta=1.0)
    algo = make_algorithm(fed, model.loss, model=model)
    state = algo.init(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1), init_batch=batch)
    pol = make_policy("uniform", M_1M, ALPHA_1M, seed=0)
    res = run_rounds(algo, state, batch, ROUNDS_1M, participation=pol,
                     store=store, aggregate="packed")
    check(res.rounds_run == ROUNDS_1M, f"{store}: {res.rounds_run} rounds")
    check(bool(np.all(res.history["selected"] == pol.n_selected)),
          f"{store}: selected {res.history['selected']}")
    return res


def phase_offload() -> None:
    t0 = time.time()
    off = run_fedpd_1m("offload")
    ex = off.extras
    f_off = off.history["f_xbar"]
    lam_off = np.asarray(jax.tree.leaves(off.state["lam"])[0])
    del off
    gc.collect()
    act = run_fedpd_1m("active")
    check(close(f_off, act.history["f_xbar"]),
          f"offload f_xbar {f_off} vs active {act.history['f_xbar']}")
    lam_act = np.asarray(jax.tree.leaves(act.state["lam"])[0])
    check(np.allclose(lam_off, lam_act, rtol=1e-5, atol=1e-6),
          "offload duals differ from the active store's")
    check(ex["host_memory_kind"] in ("tpu:pinned_host", "cpu:device"),
          f"offload store not in host memory: {ex}")
    print(f"[c] fedpd offload+packed m={M_1M} n={N_1M}: {ROUNDS_1M} rounds "
          f"match the active store | resident {ex['host_memory_kind']} "
          f"tile {ex['tile_memory_kind']} | not pinned_host because: "
          f"{ex['host_placement_reason']} | host_resident_bytes="
          f"{ex['host_resident_bytes']} device_peak_bytes="
          f"{ex['device_peak_bytes']} | {time.time() - t0:.1f}s", flush=True)


# ------------------------------------------------------- four chips
def device_stats(key: str) -> list:
    """`memory_stats()[key]` of every local device, after a collection."""
    gc.collect()
    return [d.memory_stats()[key] for d in jax.local_devices()]


def settled_bytes_in_use() -> list:
    """`bytes_in_use` of every local device once the frees of deleted
    arrays have landed: one small computation per device, then readings
    until two agree (at most 5 s)."""
    jax.block_until_ready([jax.device_put(jnp.zeros(()), d) + 1
                           for d in jax.local_devices()])
    prev = None
    for _ in range(20):
        cur = device_stats("bytes_in_use")
        if cur == prev:
            break
        prev = cur
        time.sleep(0.25)
    return cur


def spread_rows(res, shards: int) -> list:
    z = jax.tree.leaves(res.state["z"])[0]
    rows = {s.device: s.data.shape[0] for s in z.addressable_shards}
    check(len(rows) == shards and set(rows.values()) == {M_1M // shards},
          f"client rows per device: {rows}")
    return sorted(rows.values())


def phase_sharded_paper(mesh) -> list:
    """Returns the collective-budget failures (see check_budget)."""
    t0 = time.time()
    problem, n, tol = PAPER[0]
    argv = paper_argv(problem, n, tol)
    one = train_cli(argv)
    algo, state, batch = paper_algo(argv)
    parts, failures = [], []
    for overlap in ("off", "scatter"):
        res = train_cli(argv + ["--shard-clients", "4", "--overlap", overlap])
        check(res["rounds"] == one["rounds"]
              and close(res["final_f"], one["final_f"]),
              f"linreg overlap={overlap}: rounds {res['rounds']} f "
              f"{res['final_f']!r} vs one chip {one['rounds']} "
              f"{one['final_f']!r}")
        lowered, txt = round_program(algo, state, batch, mesh=mesh,
                                     overlap=overlap)
        check("tpu_custom_call" in txt, f"linreg {overlap}: no kernel")
        budget, failure = check_budget(lowered, txt, overlap,
                                       f"linreg {overlap}")
        failures += [failure] if failure else []
        parts.append(f"overlap={overlap} rounds={res['rounds']} "
                     f"f={res['final_f']!r} {budget} budget "
                     f"{'violated' if failure else 'held'}")
    print(f"[a4] linreg m={PAPER_CLIENTS} n={n} on 4 chips: one chip "
          f"rounds={one['rounds']} f={one['final_f']!r} | "
          + " | ".join(parts) + f" | {time.time() - t0:.1f}s", flush=True)
    return failures


def phase_sharded_million(mesh) -> list:
    """Returns the failed budget (see check_budget) and per-device byte
    checks."""
    t0 = time.time()
    algo, state, batch = million_fedgia()
    one = run_million(algo, state, batch)
    with_one = settled_bytes_in_use()
    f_one = one.history["f_xbar"]
    del one
    # the bytes a run's result holds are its bytes_in_use above this
    # baseline (a reading right after the build, on the chip, still
    # counted 148 MB of the build's temporaries)
    base = settled_bytes_in_use()
    one_bytes = with_one[0] - base[0]
    parts, failures = [], []
    for overlap in ("off", "scatter"):
        res = run_million(algo, state, batch, mesh=mesh, overlap=overlap)
        check(close(res.history["f_xbar"], f_one),
              f"m=1e6 overlap={overlap}: f_xbar {res.history['f_xbar']} "
              f"vs one chip {f_one}")
        rows = spread_rows(res, 4)
        per_dev = [b - b0 for b, b0 in zip(settled_bytes_in_use(), base)]
        if not all(0.15 * one_bytes < b < 0.4 * one_bytes for b in per_dev):
            failures.append(f"m=1e6 overlap={overlap}: bytes per device "
                            f"{per_dev} vs {one_bytes} on one chip")
        del res
        lowered, txt = round_program(algo, state, batch, mesh=mesh,
                                     overlap=overlap, masked=True)
        budget, failure = check_budget(lowered, txt, overlap,
                                       f"m=1e6 {overlap}")
        failures += [failure] if failure else []
        parts.append(f"overlap={overlap} rows/device={rows} "
                     f"bytes/device={per_dev} {budget} budget "
                     f"{'violated' if failure else 'held'}")
    print(f"[b4] fedgia m={M_1M} on 4 chips, f_xbar matches one chip "
          f"({[float(v) for v in f_one]}); one-chip bytes={one_bytes} | "
          + " | ".join(parts) + f" | {time.time() - t0:.1f}s", flush=True)
    return failures


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the client-sharded phases on a "
                         "four-chip host, against one chip")
    args = ap.parse_args(argv)
    use_compile_cache()
    devs = require_tpu(args.chips)
    if args.chips == 4:
        mesh = make_host_mesh(data=4)
        failures = phase_sharded_paper(mesh) + phase_sharded_million(mesh)
        check(not failures, "; ".join(failures))
    else:
        for problem, n, tol in PAPER:
            phase_paper(problem, n, tol)
        phase_million()
        phase_offload()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
