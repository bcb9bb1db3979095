"""Fault-tolerant rounds (core/faults.py + the engine's guard rail).

Four contracts pinned here (docs/faults.md):

* STRUCTURE IS FREE — a fault model at rate 0 plus the screening stage
  leaves every algorithm's history and state BITWISE unchanged on every
  path (scan/legacy × dense/active/offload): injection corrupts values,
  never the program, and the screening finite-check rides eq. (11)'s
  existing collective (the sharded round still lowers to ONE model-size
  all-reduce / {1 RS, 1 AG} — subprocess HLO assertions below).
* DEFENSE WORKS — NaN injection with screening on converges (no
  non-finite value ever reaches the psum); without screening the run
  records the divergence honestly instead of masking it.
* DEGRADATION IS RECORDED — under-quorum rounds commit nothing but the
  round counter and flag `degraded`; the divergence watchdog restores
  the best-f̄ snapshot and flags `rollback`.
* RECOVERY IS BITWISE — a checkpointed run killed mid-way and resumed
  reproduces the uninterrupted run's history and final state exactly,
  for all five algorithms (scan driver) and the offload loop; resuming
  under a different config fingerprint raises.

Fault draws are stateless (`fold_in(seed, round)` over GLOBAL row ids),
so the same faults hit the same clients on every path and across resume.
"""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import fake_device_env
from repro.config import FedConfig
from repro.core import (
    Screening,
    make_algorithm,
    make_clock,
    make_faults,
    make_policy,
    run_rounds,
)
from repro.core import engine
from repro.core.faults import FaultModel, FaultSpec, screen_rows
from repro.data import linreg_noniid
from repro.models import LeastSquares
from repro.utils import pytree as pt

M, N, D = 8, 20, 400
ROUNDS = 8

ALGO_SETUPS = {
    "fedgia": dict(sigma_t=0.2, h_policy="diag_ema", alpha=0.5),
    "fedavg": dict(lr=0.01),
    "fedprox": dict(lr=0.002, prox_mu=1e-4, inner_steps=3),
    "fedpd": dict(lr=0.05, fedpd_eta=1.0, inner_steps=3),
    "scaffold": dict(lr=0.01),
}
FIVE = sorted(ALGO_SETUPS)


@pytest.fixture(scope="module")
def problem():
    batch = {k: jnp.asarray(v) for k, v in linreg_noniid(0, D, N, M).items()}
    return LeastSquares(N), batch


def _make(problem, key, **overrides):
    model, batch = problem
    kwargs = dict(algorithm=key, num_clients=M, k0=3)
    kwargs.update(ALGO_SETUPS[key])
    kwargs.update(overrides)
    fed = FedConfig(**kwargs)
    algo = make_algorithm(fed, model.loss, model=model)
    state = algo.init(model.init(jax.random.PRNGKey(0)),
                      jax.random.PRNGKey(1), init_batch=batch)
    return algo, state


def _assert_bitwise(res, ref, *, ignore=("screened",)):
    """res must be bitwise ref, modulo metrics only res records."""
    assert res.rounds_run == ref.rounds_run
    assert set(res.history) - set(ref.history) <= set(ignore)
    for k in ref.history:
        np.testing.assert_array_equal(res.history[k], ref.history[k],
                                      err_msg=k)
    for key in ref.state:
        ok = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                          res.state[key], ref.state[key])
        assert all(jax.tree.leaves(ok)), f"state[{key!r}] diverged"


# ------------------------------------------------ fault model unit layer
def test_fault_model_draw_is_stateless_and_rate_bounded():
    fm = make_faults(["crash"], [0.5], num_clients=64, seed=3)
    rows = jnp.arange(64)
    d0 = fm.draw(jnp.int32(7), rows)
    d1 = fm.draw(jnp.int32(7), rows)
    for a, b in zip(jax.tree.leaves(d0), jax.tree.leaves(d1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a disjoint round draws a different pattern (not a constant mask)
    d2 = fm.draw(jnp.int32(8), rows)
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(d0), jax.tree.leaves(d2)))


def test_fault_model_row_split_matches_global_draw():
    """Per-client keys fold in GLOBAL row ids, so drawing for a slice of
    rows equals slicing the full draw — the property that makes faults
    identical across dense/active/offload tiles and shardings."""
    fm = make_faults(["crash", "nan"], [0.3], num_clients=32, seed=1)
    rows = jnp.arange(32)
    full = fm.draw(jnp.int32(4), rows)
    part = fm.draw(jnp.int32(4), rows[10:20])
    for kind in ("crash", "nan"):
        np.testing.assert_array_equal(np.asarray(full[kind])[10:20],
                                      np.asarray(part[kind]))


def test_make_faults_surface():
    assert make_faults([], [0.1], num_clients=4) is None
    fm = make_faults(["crash", "nan"], [0.1], num_clients=4)
    assert len(fm.specs) == 2 and all(s.rate == 0.1 for s in fm.specs)
    with pytest.raises(ValueError, match="--fault-rate"):
        make_faults(["crash", "nan", "inf"], [0.1, 0.2], num_clients=4)
    with pytest.raises(ValueError):
        FaultSpec("meteor", 0.1)
    with pytest.raises(ValueError):
        Screening(clip_norm=-1.0)
    assert FaultModel(num_clients=4,
                      specs=(FaultSpec("replay", 0.1),)).needs_prev


def test_screen_rows_drops_nonfinite_and_clips():
    contrib = jnp.asarray([[1.0, 2.0], [jnp.nan, 0.0], [30.0, 40.0],
                           [jnp.inf, 1.0]])
    mask = jnp.asarray([True, True, True, False])
    out, smask = screen_rows(contrib, mask, Screening(clip_norm=5.0))
    np.testing.assert_array_equal(np.asarray(smask),
                                  [True, False, True, False])
    assert np.all(np.isfinite(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out)[1], 0.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out)[2]), 5.0, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out)[0], [1.0, 2.0])


# ------------------------------- structure is free: rate-0 faults bitwise
@pytest.mark.parametrize("algo_key", FIVE)
def test_fault_free_rounds_bitwise_all_paths(problem, algo_key):
    """A rate-0 fault model leaves history AND state bitwise unchanged
    on scan, legacy, active and offload paths: injection corrupts
    values, never the trajectory."""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    hard = dict(faults=make_faults(["crash", "nan"], [0.0],
                                   num_clients=M, seed=5))
    paths = [dict(), dict(scan=False),
             dict(store="active"), dict(store="offload")]
    for kw in paths:
        kw = dict(kw, participation=make_policy("uniform", M, 0.5, seed=3))
        ref = run_rounds(algo, state, batch, ROUNDS, **kw)
        res = run_rounds(algo, state, batch, ROUNDS, **kw, **hard)
        _assert_bitwise(res, ref)


@pytest.mark.parametrize("algo_key", FIVE)
def test_screening_benign_data_is_a_near_noop(problem, algo_key):
    """Screening on benign (all-finite) uploads: every count metric is
    bitwise the unscreened run's and the trajectory agrees to fp
    tolerance. (Exact bitwise is NOT claimed: the finite-check rider is
    a new op in the round graph, and XLA may re-fuse neighbouring
    arithmetic — observed as 1-ulp drift on CPU. The structural claim —
    bitwise — belongs to faults=None/screening=None, pinned above.)"""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    pol = make_policy("uniform", M, 0.5, seed=3)
    ref = run_rounds(algo, state, batch, ROUNDS, participation=pol)
    res = run_rounds(algo, state, batch, ROUNDS, participation=pol,
                     screening=Screening())
    assert res.rounds_run == ref.rounds_run
    for k in ("selected", "cr", "local_grad_evals"):
        np.testing.assert_array_equal(res.history[k], ref.history[k],
                                      err_msg=k)
    for k in ("f_xbar", "grad_sq_norm"):
        np.testing.assert_allclose(res.history[k], ref.history[k],
                                   rtol=1e-5, atol=1e-7, err_msg=k)
    # nothing was screened out: FedGiA uploads the whole population's z
    # (screened mask starts from all m rows), the baselines upload the
    # participants only
    expect = (np.full(ROUNDS, float(M)) if algo_key == "fedgia"
              else ref.history["selected"])
    np.testing.assert_array_equal(res.history["screened"], expect)


def test_replay_faults_scan_matches_legacy(problem):
    """The replay fault carries last round's honest upload in the round
    state (`fault_prev`) — the stateful-est injection path must still be
    bitwise across scan/legacy."""
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    kw = dict(participation=make_policy("uniform", M, 0.5, seed=3),
              faults=make_faults(["replay"], [0.3], num_clients=M, seed=7),
              screening=Screening())
    ref = run_rounds(algo, state, batch, ROUNDS, **kw)
    res = run_rounds(algo, state, batch, ROUNDS, scan=False, **kw)
    for k in ref.history:
        np.testing.assert_array_equal(res.history[k], ref.history[k],
                                      err_msg=k)


# ------------------------------------------------- defense & degradation
def test_nan_injection_converges_with_screening(problem):
    algo, state = _make(problem, "fedgia")
    _, batch = problem
    res = run_rounds(algo, state, batch, 20,
                     participation=make_policy("uniform", M, 0.5, seed=3),
                     faults=make_faults(["nan", "inf"], [0.2],
                                        num_clients=M, seed=11),
                     screening=Screening())
    f = res.history["f_xbar"]
    assert np.all(np.isfinite(f))
    assert f[-1] < f[0]
    # screening visibly dropped uploads in at least one round
    assert (res.history["screened"] < res.history["selected"]).any()


def test_nan_injection_recorded_honestly_without_screening(problem):
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    res = run_rounds(algo, state, batch, 12,
                     participation=make_policy("uniform", M, 0.5, seed=3),
                     faults=make_faults(["nan"], [0.5],
                                        num_clients=M, seed=11))
    assert not np.all(np.isfinite(res.history["f_xbar"]))


def test_quorum_degrades_rounds_to_recorded_noops(problem):
    algo, state = _make(problem, "scaffold")
    _, batch = problem
    res = run_rounds(algo, state, batch, 16,
                     participation=make_policy("uniform", M, 0.5, seed=3),
                     faults=make_faults(["crash"], [0.5],
                                        num_clients=M, seed=2),
                     screening=Screening(), quorum=2)
    deg = res.history["degraded"]
    assert deg.dtype == bool and deg.any() and not deg.all()
    assert np.all(np.isfinite(res.history["f_xbar"]))
    assert res.rounds_run == 16  # degraded rounds still advance the run


def test_watchdog_rolls_back_under_explosions(problem):
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    res = run_rounds(algo, state, batch, 24,
                     participation=make_policy("uniform", M, 0.5, seed=3),
                     faults=make_faults(["explode"], [0.3],
                                        num_clients=M, seed=4),
                     watchdog=True, watchdog_patience=2)
    assert res.history["rollback"].sum() >= 1
    # the final state is a real (restored or surviving) state, not junk
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree.leaves(res.state["x"]))


def test_watchdog_quiet_run_never_fires(problem):
    algo, state = _make(problem, "fedgia")
    _, batch = problem
    kw = dict(participation=make_policy("uniform", M, 0.5, seed=3))
    ref = run_rounds(algo, state, batch, ROUNDS, **kw)
    res = run_rounds(algo, state, batch, ROUNDS, watchdog=True, **kw)
    assert res.history["rollback"].sum() == 0
    _assert_bitwise(res, ref, ignore=("rollback",))


def test_deadline_clock_rounds_advance_by_deadline(problem):
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    clock = make_clock("constant", M,
                       compute_s=[1.0 + (i % 4) for i in range(M)],
                       deadline_s=2.5)
    res = run_rounds(algo, state, batch, ROUNDS, clock=clock, quorum=1)
    np.testing.assert_allclose(res.history["sim_time"],
                               2.5 * np.arange(1, ROUNDS + 1), rtol=1e-6)
    # the slow clients (3-4 s compute) miss their round's 2.5 s deadline
    # and re-arrive a LATER round: arrivals oscillate below/at m
    assert (res.history["selected"] < M).any()
    assert res.history["selected"].min() >= 1
    with pytest.raises(ValueError, match="quorum >= 1"):
        run_rounds(algo, state, batch, 2, clock=clock)


# ----------------------------------------------- engine validation layer
def test_engine_rejections(problem, tmp_path):
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    pol = make_policy("uniform", M, 0.5, seed=3)
    with pytest.raises(ValueError, match="non-arrival"):
        run_rounds(algo, state, batch, 2, quorum=2)
    with pytest.raises(ValueError, match="quorum must be in"):
        run_rounds(algo, state, batch, 2, participation=pol, quorum=M + 1)
    with pytest.raises(ValueError, match="watchdog_patience"):
        run_rounds(algo, state, batch, 2, watchdog=True,
                   watchdog_patience=0)
    with pytest.raises(ValueError, match="watchdog_factor"):
        run_rounds(algo, state, batch, 2, watchdog=True,
                   watchdog_factor=1.0)
    with pytest.raises(ValueError, match="host-resident"):
        run_rounds(algo, state, batch, 2, participation=pol,
                   store="offload", watchdog=True)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        run_rounds(algo, state, batch, 2, checkpoint_every=1)
    with pytest.raises(ValueError, match="chunk"):
        run_rounds(algo, state, batch, 2, checkpoint_every=1,
                   checkpoint_dir=str(tmp_path), chunk_size="auto")
    with pytest.raises(ValueError, match="scan driver"):
        run_rounds(algo, state, batch, 2, scan=False, checkpoint_every=1,
                   checkpoint_dir=str(tmp_path))
    with pytest.raises(ValueError, match="flat"):
        run_rounds(algo, state, batch, 2, flat=False,
                   faults=make_faults(["crash"], [0.1], num_clients=M))
    with pytest.raises(ValueError, match="clients"):
        run_rounds(algo, state, batch, 2,
                   faults=make_faults(["crash"], [0.1], num_clients=M + 1))


# --------------------------------------------- recovery: bitwise resume
@pytest.mark.parametrize("algo_key", FIVE)
def test_checkpoint_resume_bitwise_scan(problem, algo_key, tmp_path):
    """Kill at round 6 of 12 (checkpoints every 4), resume to 12: the
    resumed run's history and final state are BITWISE the uninterrupted
    run's — with faults on, so the stateless draws line up across the
    restart too."""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    kw = dict(participation=make_policy("uniform", M, 0.5, seed=3),
              faults=make_faults(["crash", "explode"], [0.2],
                                 num_clients=M, seed=9),
              screening=Screening(clip_norm=1e3))
    ref = run_rounds(algo, state, batch, 12, **kw)
    d = str(tmp_path / algo_key)
    run_rounds(algo, state, batch, 6, checkpoint_every=4,
               checkpoint_dir=d, **kw)
    res = run_rounds(algo, state, batch, 12, checkpoint_every=4,
                     checkpoint_dir=d, resume=True, **kw)
    _assert_bitwise(res, ref, ignore=())


def test_checkpoint_resume_bitwise_offload(problem, tmp_path):
    algo, state = _make(problem, "scaffold")
    _, batch = problem
    kw = dict(participation=make_policy("uniform", M, 0.5, seed=3),
              store="offload", quorum=1,
              faults=make_faults(["crash"], [0.3], num_clients=M, seed=9),
              screening=Screening())
    ref = run_rounds(algo, state, batch, 12, **kw)
    d = str(tmp_path / "offload")
    run_rounds(algo, state, batch, 6, checkpoint_every=4,
               checkpoint_dir=d, **kw)
    res = run_rounds(algo, state, batch, 12, checkpoint_every=4,
                     checkpoint_dir=d, resume=True, **kw)
    _assert_bitwise(res, ref, ignore=())


def test_resume_rejects_fingerprint_mismatch(problem, tmp_path):
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    pol = make_policy("uniform", M, 0.5, seed=3)
    d = str(tmp_path / "fp")
    run_rounds(algo, state, batch, 4, participation=pol,
               checkpoint_every=2, checkpoint_dir=d)
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        run_rounds(algo, state, batch, 8, participation=pol, quorum=2,
                   checkpoint_every=2, checkpoint_dir=d, resume=True)
    # extending num_rounds is NOT a mismatch — that is the point
    res = run_rounds(algo, state, batch, 8, participation=pol,
                     checkpoint_every=2, checkpoint_dir=d, resume=True)
    assert res.rounds_run == 8


def test_resume_without_checkpoint_is_fresh_start(problem, tmp_path):
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    pol = make_policy("uniform", M, 0.5, seed=3)
    ref = run_rounds(algo, state, batch, ROUNDS, participation=pol)
    res = run_rounds(algo, state, batch, ROUNDS, participation=pol,
                     checkpoint_every=4, resume=True,
                     checkpoint_dir=str(tmp_path / "empty"))
    _assert_bitwise(res, ref, ignore=())


# ---------------------------------- legacy-loop donation (and its proof)
def test_legacy_donated_rounds_bitwise(problem):
    """donate=True on the legacy loop (AOT + donated state/anchor/
    watchdog args) is bitwise the undonated loop."""
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    kw = dict(participation=make_policy("uniform", M, 0.5, seed=3),
              scan=False, watchdog=True)
    ref = run_rounds(algo, state, batch, ROUNDS, donate=False, **kw)
    res = run_rounds(algo, state, batch, ROUNDS, donate=True, **kw)
    _assert_bitwise(res, ref, ignore=())


@pytest.mark.parametrize("algo_key", ["fedavg", "scaffold"])
def test_legacy_donation_no_model_size_temp_growth(problem, algo_key):
    """`memory_analysis` proof for the baselines' flat GD rounds:
    off-CPU the donated lowering allocates no more temp than the
    undonated one and aliases at least the (m, N) client state onto
    outputs. On CPU, XLA cannot alias — donation is a no-op there and
    the annotation alone perturbs fusion/temp bytes by a few KB — so
    on CPU this is a compile smoke only (the donated loop's numerics
    are covered by test_legacy_donated_rounds_bitwise)."""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    spec = pt.ravel_spec(state["x"])
    fstate = engine.flatten_state(algo, state, spec)
    rf = engine.make_round_fn(algo, flat_spec=spec)
    don = jax.jit(rf, donate_argnums=(0,)).lower(
        fstate, batch).compile().memory_analysis()
    und = jax.jit(rf).lower(fstate, batch).compile().memory_analysis()
    if jax.default_backend() != "cpu":
        assert don.temp_size_in_bytes <= und.temp_size_in_bytes
        client_bytes = sum(
            int(np.asarray(fstate[k]).nbytes)
            for k in getattr(algo, "flat_client_keys", ()) if k in fstate)
        assert don.alias_size_in_bytes >= client_bytes


# ------------------------------ host transfers raise (utils/pytree.py)
def test_host_put_failure_raises(monkeypatch):
    """A failed transfer to the offload placement surfaces as the error it
    is: no retry, and the store is never moved to another backend behind
    the run's back."""
    placement = pt.host_placement()

    def dead(x, device=None, **kw):
        raise RuntimeError("simulated dead DMA path")

    monkeypatch.setattr(jax, "device_put", dead)
    with pytest.raises(RuntimeError, match="simulated dead DMA path"):
        pt.host_put(jnp.ones((3,)))
    monkeypatch.undo()
    assert pt.host_placement() is placement


# ----------------------- sharded: screening rides the ONE collective
_SHARDED_FAULT_SCRIPT = textwrap.dedent(
    """
    import jax, jax.numpy as jnp
    from hlo_guard import assert_barrier_round, assert_overlap_round
    from repro.config import FedConfig
    from repro.core import engine, make_algorithm, make_faults, Screening
    from repro.data import linreg_noniid
    from repro.launch.mesh import make_host_mesh
    from repro.models import LeastSquares
    from repro.utils import pytree as pt

    m, n, d = 8, 24, 320
    batch = {k: jnp.asarray(v) for k, v in linreg_noniid(0, d, n, m).items()}
    model = LeastSquares(n)
    mesh = make_host_mesh(data=8)
    hard = dict(faults=make_faults(["crash", "nan"], [0.1],
                                   num_clients=m, seed=1),
                screening=Screening(clip_norm=100.0))

    def round_hlo(algo_name, **kw):
        fed = FedConfig(algorithm=algo_name, num_clients=m, k0=3, alpha=0.5,
                        sigma_t=0.3, h_policy="diag_ema", lr=0.01)
        algo = make_algorithm(fed, model.loss, model=model)
        s0 = algo.init(model.init(jax.random.PRNGKey(0)),
                       jax.random.PRNGKey(1), init_batch=batch)
        spec = pt.ravel_spec(s0["x"])
        s0f = engine.flatten_state(algo, s0, spec)
        if kw.get("overlap"):
            rows = int(getattr(algo, "overlap_slot_rows", 1))
            s0f["ovl_shard"] = jnp.zeros((rows, spec.padded_size),
                                         s0f["x"].dtype)
        rf = engine.make_round_fn(algo, mesh, masked=True, flat_spec=spec,
                                  **hard, **kw)
        st, b = engine.shard_inputs(algo, s0f, batch, mesh)
        return jax.jit(rf).lower(st, b, jnp.ones((m,), bool)
                                 ).compile().as_text()

    for name in ("fedgia", "fedavg", "fedprox", "fedpd", "scaffold"):
        assert_barrier_round(round_hlo(name), name)
    assert_overlap_round(round_hlo("fedgia", overlap="scatter"), "overlap")
    print("FAULT_SHARDED_OK screening rides the one collective")
    """
)


def test_sharded_screening_keeps_one_collective():
    """With faults + screening threaded in, the sharded round still
    lowers to exactly ONE model-size all-reduce (barrier) for all five
    algorithms, and the overlapped FedGiA round to {1 RS, 1 AG} — the
    finite-check/clip/count are riders on the existing collectives."""
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_FAULT_SCRIPT],
        env=fake_device_env(8), capture_output=True, text=True, timeout=900,
    )
    assert "FAULT_SHARDED_OK" in out.stdout, out.stdout + out.stderr
