"""Active-set client store equivalence (engine `store="active"`).

The active store swaps the dense (m, N) round working set for a packed
(capacity, N) tile of the round's participants: gathered from the
resident flat buffers at the round start, scattered back at the end.
STATE must be BITWISE the dense store's on every single-device path —
the tile rows are the same trajectories (row-position-independent math)
and the aggregation scatters back to the dense layout before reducing,
so eq. (11) sees bit-identical inputs through the same compiled reduce
(api.flat_round_aggregate_active). The loss/gradient DIAGNOSTICS differ
by construction: the server never contacts frozen clients, so `f_xbar`
and `grad_sq_norm` become participant means (docs/engine.md). FedGiA
declares `active_tile="population"` (its GD branch rewrites every
client every round) and falls back to the dense round — for it the
whole history is bitwise too.

Also covers: ActiveSet packing/gather/scatter units, the engine's
store validation, auto-chunk composition, and (subprocess) the zero-tail
debug assertion (REPRO_DEBUG_TAIL=1) plus the sharded active round's
ONE model-size all-reduce.

The HOST-OFFLOADED store (`store="offload"`) moves the resident client
buffers + batch + stale anchor into host memory and shuttles (capacity,
N) tiles per round; host gather/scatter is pure data movement, so it
must be BITWISE `store="active"` on every path — including the full
metric history (same tile bits through same-shaped reductions). The
PACKED aggregation (`aggregate="packed"`) sums the participant tile
directly and is held to fp tolerance against the dense layout, with the
sharded packed round still lowering to ONE model-size all-reduce.
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
from repro.core import make_algorithm, make_policy, run_rounds
from repro.core.clock import ComputeClock
from repro.data import linreg_noniid
from repro.models import LeastSquares
from repro.utils import pytree as pt

M, N, D = 8, 20, 400
ROUNDS = 10

ALGO_SETUPS = {
    "fedgia": dict(sigma_t=0.2, h_policy="diag_ema", alpha=0.5),
    "fedavg": dict(lr=0.01),
    "fedprox": dict(lr=0.002, prox_mu=1e-4, inner_steps=3),
    "fedpd": dict(lr=0.05, fedpd_eta=1.0, inner_steps=3),
    "scaffold": dict(lr=0.01),
}
FIVE = sorted(ALGO_SETUPS)

# metrics that must match bitwise between stores for EVERY algorithm;
# f_xbar / grad_sq_norm are participant means under the active store and
# only match for population-tile algorithms (fedgia)
COMPARABLE = ("selected", "cr", "local_grad_evals", "staleness",
              "staleness_max", "sim_time")


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


def _assert_store_equiv(res, ref, algo):
    """Active (res) vs dense (ref): bitwise state, bitwise comparable
    metrics; the full history bitwise for population-tile algorithms."""
    assert res.rounds_run == ref.rounds_run
    assert set(res.history) == set(ref.history)
    full = getattr(algo, "active_tile", "participants") == "population"
    for k in ref.history:
        if full or k in COMPARABLE:
            np.testing.assert_array_equal(res.history[k], ref.history[k],
                                          err_msg=k)
    for key in ref.state:
        ok = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                          res.state[key], ref.state[key])
        assert all(jax.tree.leaves(ok)), f"state[{key!r}] diverged"


def _run_pair(algo, state, batch, **kw):
    ref = run_rounds(algo, state, batch, ROUNDS, store="dense", **kw)
    res = run_rounds(algo, state, batch, ROUNDS, store="active", **kw)
    return res, ref


# ------------------------------------------------- ActiveSet pack/gather
def test_make_active_set_packs_ascending_with_sentinel_padding():
    mask = jnp.asarray([0, 1, 0, 1, 1, 0, 0, 1], bool)
    aset = pt.make_active_set(mask, capacity=6)
    np.testing.assert_array_equal(np.asarray(aset.idx),
                                  [1, 3, 4, 7, 8, 8])  # sentinel = m
    np.testing.assert_array_equal(np.asarray(aset.valid),
                                  [1, 1, 1, 1, 0, 0])
    assert float(aset.count) == 4.0
    assert aset.capacity == 6 and aset.num_clients == 8


def test_gather_scatter_roundtrip_leaves_frozen_rows():
    mask = jnp.asarray([0, 1, 0, 1], bool)
    aset = pt.make_active_set(mask, capacity=2)
    buf = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    tile = aset.gather(buf)
    np.testing.assert_array_equal(np.asarray(tile), np.asarray(buf)[[1, 3]])
    out = aset.scatter(buf, tile * 10.0)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(buf[0]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(buf[2]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(buf[1]) * 10)
    # padding rows (sentinel index) are DROPPED at the scatter
    aset1 = pt.make_active_set(jnp.asarray([0, 1, 0, 0], bool), capacity=3)
    out = aset1.scatter(buf, jnp.full((3, 3), -1.0))
    np.testing.assert_array_equal(np.asarray(out[1]), -np.ones(3))
    for r in (0, 2, 3):
        np.testing.assert_array_equal(np.asarray(out[r]), np.asarray(buf[r]))


def test_zero_invalid_zeroes_padding_rows_only():
    aset = pt.make_active_set(jnp.asarray([1, 0, 0, 1], bool), capacity=3)
    tile = jnp.ones((3, 5))
    z = np.asarray(aset.zero_invalid(tile))
    np.testing.assert_array_equal(z[:2], np.ones((2, 5)))
    np.testing.assert_array_equal(z[2], np.zeros(5))


def test_policy_active_capacity_and_indices():
    pol = make_policy("uniform", M, 0.5, seed=0)
    assert pol.active_capacity == pol.n_selected == M // 2
    aset, _ = pol.indices(pol.init(), 0)
    assert aset.capacity == M // 2
    assert bool(jnp.all(aset.valid))  # uniform fills the tile exactly
    # variable-cardinality policies bound the tile by m
    strag = make_policy("straggler", M, seed=0, drop_prob=0.3, horizon=8)
    assert strag.active_capacity == M


# ------------------------------------------------ active == dense, masked
@pytest.mark.parametrize("algo_key", FIVE)
def test_active_matches_dense_masked_scan(problem, algo_key):
    algo, state = _make(problem, algo_key)
    _, batch = problem
    res, ref = _run_pair(algo, state, batch,
                         participation=make_policy("uniform", M, 0.5, seed=3))
    _assert_store_equiv(res, ref, algo)


@pytest.mark.parametrize("algo_key", FIVE)
def test_active_matches_dense_masked_legacy(problem, algo_key):
    algo, state = _make(problem, algo_key)
    _, batch = problem
    res, ref = _run_pair(algo, state, batch, scan=False,
                         participation=make_policy("uniform", M, 0.5, seed=3))
    _assert_store_equiv(res, ref, algo)


@pytest.mark.parametrize("kind", ["cyclic", "weighted", "straggler"])
def test_active_matches_dense_other_policies(problem, kind):
    """Fixed-cardinality tiles (cyclic/weighted) and the variable-
    cardinality m-bound tile (straggler) all stay bitwise."""
    algo, state = _make(problem, "scaffold")
    _, batch = problem
    res, ref = _run_pair(
        algo, state, batch,
        participation=make_policy(kind, M, 0.5, seed=1, drop_prob=0.3,
                                  horizon=ROUNDS))
    _assert_store_equiv(res, ref, algo)


# --------------------------------------------------- async / clocked paths
@pytest.mark.parametrize("algo_key", ["fedavg", "scaffold", "fedgia"])
def test_active_matches_dense_async(problem, algo_key):
    """Stale-x̄ rounds: ages stay dense (m,) scalars, the anchor tile is
    gathered with force-refresh, and the resident anchor buffer takes one
    dense row-select per round — bitwise the dense async engine,
    including the per-round `staleness` history."""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    pol = make_policy("periodic", M)
    res, ref = _run_pair(algo, state, batch, participation=pol,
                         async_rounds=True, max_staleness=2)
    _assert_store_equiv(res, ref, algo)


def test_active_matches_dense_async_zero_staleness(problem):
    algo, state = _make(problem, "fedpd")
    _, batch = problem
    res, ref = _run_pair(algo, state, batch,
                         participation=make_policy("periodic", M),
                         async_rounds=True, max_staleness=0)
    _assert_store_equiv(res, ref, algo)


@pytest.mark.parametrize("algo_key", ["fedavg", "scaffold"])
def test_active_matches_dense_clocked_weighted(problem, algo_key):
    """Wall-clock arrivals (tile capacity = m) with staleness-weighted
    aggregation: the dense weights enter the aggregate as the same
    masked (m,) vector, so the weighted eq. (11) stays bitwise."""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    clk = ComputeClock(M, 1.0 + (np.arange(M) % 3))
    res, ref = _run_pair(algo, state, batch, clock=clk, max_staleness=3,
                         stale_weighting="poly", stale_decay=0.5)
    _assert_store_equiv(res, ref, algo)


# --------------------------------------------------- engine knob composure
def test_active_chunk_auto_matches_fixed(problem):
    """`--chunk auto` composes with the active store: the tile
    gather/scatter runs inside every round whatever the chunk length, so
    the autotuned run is bitwise the fixed-chunk active run."""
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    pol = lambda: make_policy("uniform", M, 0.5, seed=3)
    ref = run_rounds(algo, state, batch, 60, chunk_size=7,
                     participation=pol(), store="active")
    res = run_rounds(algo, state, batch, 60, chunk_size="auto",
                     participation=pol(), store="active")
    assert res.rounds_run == ref.rounds_run == 60
    for k in ref.history:
        np.testing.assert_array_equal(res.history[k], ref.history[k],
                                      err_msg=k)


def test_active_early_stop_scan_matches_legacy(problem):
    """Under the active store the tol rule gates on the PARTICIPANT
    gradient norm (the population one is unobservable) — scan and legacy
    still stop on the same round with the same state."""
    algo, state = _make(problem, "fedgia", k0=5)
    _, batch = problem
    kw = dict(tol=1e-9, participation=make_policy("uniform", M, 0.5, seed=3),
              store="active")
    ref = run_rounds(algo, state, batch, 300, chunk_size=13, scan=False, **kw)
    res = run_rounds(algo, state, batch, 300, chunk_size=13, **kw)
    assert ref.stopped_early and res.stopped_early
    assert res.rounds_run == ref.rounds_run
    for key in ref.state:
        ok = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                          res.state[key], ref.state[key])
        assert all(jax.tree.leaves(ok)), key


def test_store_validation(problem):
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    pol = make_policy("uniform", M, 0.5, seed=0)
    with pytest.raises(ValueError, match="unknown store"):
        run_rounds(algo, state, batch, 2, store="sparse", participation=pol)
    with pytest.raises(ValueError, match="flat"):
        run_rounds(algo, state, batch, 2, store="active", participation=pol,
                   flat=False)
    with pytest.raises(ValueError, match="participant"):
        run_rounds(algo, state, batch, 2, store="active")


# --------------------------------------------- zero-tail debug assertion
_TAIL_SCRIPT = textwrap.dedent(
    """
    import jax, jax.numpy as jnp
    from repro.utils import pytree as pt
    assert pt.DEBUG_TAIL, "REPRO_DEBUG_TAIL not picked up"
    tree = {"w": jnp.ones((5,)), "b": jnp.zeros(())}
    spec = pt.ravel_spec(tree)
    assert spec.padded_size > spec.size  # lane padding present
    flat = spec.ravel(tree)
    spec.unravel(flat)  # clean tail passes
    jax.block_until_ready(jax.tree.leaves(spec.unravel(flat)))
    print("CLEAN_OK")
    bad = flat.at[spec.padded_size - 1].set(3.0)  # corrupt the pad lane
    try:
        jax.block_until_ready(jax.tree.leaves(spec.unravel(bad)))
        print("CORRUPTION_MISSED")
    except Exception:
        print("CORRUPTION_CAUGHT")
    """
)


def test_debug_tail_assertion_catches_corruption():
    """REPRO_DEBUG_TAIL=1 turns every unravel into a zero-tail audit: a
    clean flat buffer passes, a corrupted pad lane raises. Subprocess —
    the flag is read at import and must not leak into this session."""
    import os

    env = dict(os.environ, REPRO_DEBUG_TAIL="1", JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", "src")
    out = subprocess.run(
        [sys.executable, "-c", _TAIL_SCRIPT], env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert "CLEAN_OK" in out.stdout, out.stdout + out.stderr
    assert "CORRUPTION_CAUGHT" in out.stdout, out.stdout + out.stderr


def test_round_flat_active_keeps_zero_tail(problem):
    """The scatter path preserves the RavelSpec zero-tail invariant: after
    active rounds every resident flat buffer still has an exactly-zero
    pad tail (gathered tiles inherit it, local steps keep padded lanes at
    +0.0, and the scatter writes only participant rows)."""
    from repro.core.engine import flatten_state

    algo, state = _make(problem, "scaffold")
    _, batch = problem
    res = run_rounds(algo, state, batch, ROUNDS, store="active",
                     participation=make_policy("uniform", M, 0.5, seed=3))
    spec = pt.ravel_spec(state["x"])
    flat = flatten_state(algo, res.state, spec)
    for k in ("x", "c"):
        assert float(jnp.abs(flat[k][spec.size:]).max()) == 0.0, k
    assert float(jnp.abs(flat["ci"][:, spec.size:]).max()) == 0.0


# ------------------------------------- sharded: ONE model-size all-reduce
_SHARDED_ACTIVE_SCRIPT = textwrap.dedent(
    """
    import jax, jax.numpy as jnp, numpy as np
    from hlo_guard import assert_barrier_round
    from repro.config import FedConfig
    from repro.core import api, engine, make_algorithm, make_policy, run_rounds
    from repro.data import linreg_noniid
    from repro.launch.mesh import make_host_mesh
    from repro.models import LeastSquares
    from repro.utils import pytree as pt

    m, n, d = 8, 24, 320
    batch = {k: jnp.asarray(v) for k, v in linreg_noniid(0, d, n, m).items()}
    model = LeastSquares(n)
    mesh = make_host_mesh(data=8)

    def round_hlo(algo_name):
        fed = FedConfig(algorithm=algo_name, num_clients=m, k0=3, alpha=0.5,
                        sigma_t=0.3, h_policy="diag_ema", lr=0.01)
        algo = make_algorithm(fed, model.loss, model=model)
        s0 = algo.init(model.init(jax.random.PRNGKey(0)),
                       jax.random.PRNGKey(1), init_batch=batch)
        spec = pt.ravel_spec(s0["x"])
        s0f = engine.flatten_state(algo, s0, spec)
        cap = make_policy("uniform", m, 0.5).active_capacity
        rf = engine.make_round_fn(algo, mesh, masked=True, flat_spec=spec,
                                  active_capacity=cap)
        st, b = engine.shard_inputs(algo, s0f, batch, mesh)
        return jax.jit(rf).lower(st, b, jnp.ones((m,), bool)
                                 ).compile().as_text()

    for name in ("fedgia", "fedavg", "fedprox", "fedpd", "scaffold"):
        assert_barrier_round(round_hlo(name), name)

    # and the sharded active RUN matches the single-device active run
    fed = FedConfig(algorithm="scaffold", num_clients=m, k0=3, lr=0.01)
    algo = make_algorithm(fed, model.loss, model=model)
    s0 = algo.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1),
                   init_batch=batch)
    kw = dict(participation=make_policy("uniform", m, 0.5, seed=3),
              store="active")
    ref = run_rounds(algo, s0, batch, 10, **kw)
    res = run_rounds(algo, s0, batch, 10, mesh=mesh, **kw)
    for k in ref.history:
        np.testing.assert_allclose(res.history[k], ref.history[k],
                                   rtol=1e-4, atol=1e-6, err_msg=k)
    print("ACTIVE_SHARDED_OK one model-size all-reduce for all five")
    """
)


def test_active_sharded_one_all_reduce_and_parity():
    """The sharded active round packs per shard (capacity clamped to
    m_local) and still lowers to exactly ONE model-size all-reduce for
    all five algorithms; the sharded active run matches the single-device
    active run to fp tolerance."""
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_ACTIVE_SCRIPT],
        env=fake_device_env(8), capture_output=True, text=True, timeout=900,
    )
    assert "ACTIVE_SHARDED_OK" in out.stdout, out.stdout + out.stderr


# ------------------------------------------- offload == active, bitwise
def _assert_offload_equiv(res, ref):
    """Offload (res) vs active (ref): bitwise state AND bitwise full
    history — host gather/scatter is pure data movement, so every tile
    entering the round carries the active store's exact bits and every
    metric leaves through the same-shaped reductions."""
    assert res.rounds_run == ref.rounds_run
    assert set(res.history) == set(ref.history)
    for k in ref.history:
        np.testing.assert_array_equal(res.history[k], ref.history[k],
                                      err_msg=k)
    for key in ref.state:
        ok = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                          res.state[key], ref.state[key])
        assert all(jax.tree.leaves(ok)), f"state[{key!r}] diverged"


def _run_offload_pair(algo, state, batch, **kw):
    ref = run_rounds(algo, state, batch, ROUNDS, store="active", **kw)
    res = run_rounds(algo, state, batch, ROUNDS, store="offload", **kw)
    return res, ref


@pytest.mark.parametrize("use_scan", [True, False], ids=["scan", "legacy"])
@pytest.mark.parametrize("algo_key", FIVE)
def test_offload_matches_active_masked(problem, algo_key, use_scan):
    """5 algos x scan/legacy under uniform masked participation: the
    host-resident tiles replay the active store bit for bit (FedGiA's
    population tile shuttles the full buffers instead)."""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    res, ref = _run_offload_pair(
        algo, state, batch, scan=use_scan,
        participation=make_policy("uniform", M, 0.5, seed=3))
    _assert_offload_equiv(res, ref)


@pytest.mark.parametrize("algo_key", FIVE)
def test_offload_matches_active_async(problem, algo_key):
    """Stale-x̄ rounds: the anchor buffer rides host memory and the
    engine applies the dense refresh write host-side (`anchor[refresh] =
    x̄` — the view's exact row select), ages stay device (m,) riders."""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    res, ref = _run_offload_pair(algo, state, batch,
                                 participation=make_policy("periodic", M),
                                 async_rounds=True, max_staleness=2)
    _assert_offload_equiv(res, ref)


def test_offload_matches_active_async_zero_staleness(problem):
    """max_staleness=0 (always fresh): the host anchor is never read or
    written — still bitwise the active engine."""
    algo, state = _make(problem, "fedpd")
    _, batch = problem
    res, ref = _run_offload_pair(algo, state, batch,
                                 participation=make_policy("periodic", M),
                                 async_rounds=True, max_staleness=0)
    _assert_offload_equiv(res, ref)


@pytest.mark.parametrize("algo_key", ["fedavg", "scaffold"])
def test_offload_matches_active_clocked_weighted(problem, algo_key):
    """Wall-clock arrivals (tile capacity = m) + staleness-weighted
    eq. (11): the dense (m,) weights stay device-resident and gather by
    REAL row ids inside the tile round — bitwise the active store."""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    clk = ComputeClock(M, 1.0 + (np.arange(M) % 3))
    res, ref = _run_offload_pair(algo, state, batch, clock=clk,
                                 max_staleness=3, stale_weighting="poly",
                                 stale_decay=0.5)
    _assert_offload_equiv(res, ref)


def test_offload_ef_stale_composition(problem):
    """EF residuals ride the host store: the codec's residual tile is
    gathered/advanced/scattered through the same host rows as any client
    state, composed with staleness and the byte-accurate wire clock —
    bitwise the active store, bytes_up included."""
    algo, state = _make(problem, "scaffold")
    _, batch = problem
    clk = ComputeClock(M, 1.0 + (np.arange(M) % 3), bandwidth_bps=1e6)
    res, ref = _run_offload_pair(algo, state, batch, clock=clk,
                                 max_staleness=2, compression="int8",
                                 error_feedback=True)
    _assert_offload_equiv(res, ref)


def test_offload_early_stop_matches_active(problem):
    """The offload loop's per-round host sync applies the eq.-(35) tol
    rule on the same metric stream — same stop round, same state."""
    algo, state = _make(problem, "fedgia", k0=5)
    _, batch = problem
    kw = dict(tol=1e-9, participation=make_policy("uniform", M, 0.5, seed=3))
    ref = run_rounds(algo, state, batch, 300, scan=False, store="active",
                     **kw)
    res = run_rounds(algo, state, batch, 300, store="offload", **kw)
    assert ref.stopped_early and res.stopped_early
    assert res.rounds_run == ref.rounds_run
    for key in ref.state:
        ok = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                          res.state[key], ref.state[key])
        assert all(jax.tree.leaves(ok)), key


def test_offload_reports_memory_extras(problem):
    """RoundResult.extras carries the offload footprint: the buffers that
    left the device and (where XLA reports it) the compiled tile round's
    peak device bytes."""
    algo, state = _make(problem, "fedpd")
    _, batch = problem
    res = run_rounds(algo, state, batch, 3, store="offload",
                     participation=make_policy("uniform", M, 0.5, seed=3))
    assert res.extras["host_resident_bytes"] > 0
    peak = res.extras["device_peak_bytes"]
    assert peak is None or peak > 0
    # dense/active paths don't populate extras
    ref = run_rounds(algo, state, batch, 3, store="active",
                     participation=make_policy("uniform", M, 0.5, seed=3))
    assert ref.extras == {}


def test_offload_reports_host_placement(problem):
    """RoundResult.extras names where the store went: the memory kind of
    the resident buffers and of a host-gathered tile, and why the store
    is not in pinned host memory (on a CPU process: the CPU backend)."""
    algo, state = _make(problem, "fedpd")
    _, batch = problem
    res = run_rounds(algo, state, batch, 2, store="offload",
                     participation=make_policy("uniform", M, 0.5, seed=3))
    ex = res.extras
    assert ex["host_memory_kind"] == pt.memory_kind(pt.host_put(jnp.ones(2)))
    assert ex["tile_memory_kind"] == ex["host_memory_kind"]
    if jax.default_backend() == "cpu":
        assert ex["host_memory_kind"] == "cpu:device"
        assert "CPU" in ex["host_placement_reason"]
    else:
        assert (ex["host_placement_reason"] is None) == (
            ex["host_memory_kind"].endswith(":pinned_host"))


def test_tile_state_accessors_are_identity():
    """tile_state=True: gather_state/scatter_state pass pre-gathered
    tiles through; plain gather/scatter keep REAL resident row
    semantics (the dense riders and the aggregation depend on it)."""
    mask = jnp.asarray([0, 1, 0, 1], bool)
    aset = pt.make_active_set(mask, capacity=2, tile_state=True)
    tile = jnp.arange(6, dtype=jnp.float32).reshape(2, 3)
    assert aset.gather_state(tile) is tile
    assert aset.scatter_state(tile, tile * 2) is not tile
    np.testing.assert_array_equal(np.asarray(aset.scatter_state(tile,
                                                                tile * 2)),
                                  np.asarray(tile) * 2)
    dense = jnp.arange(4, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(aset.gather(dense)),
                                  [1.0, 3.0])
    # resident mode: gather_state == gather
    rset = pt.make_active_set(mask, capacity=2)
    buf = jnp.arange(12, dtype=jnp.float32).reshape(4, 3)
    np.testing.assert_array_equal(np.asarray(rset.gather_state(buf)),
                                  np.asarray(rset.gather(buf)))


def test_offload_store_roundtrip_bitwise():
    """OffloadStore gather/scatter == the device store's
    gather_rows/scatter_rows, bit for bit (clip reads, drop writes)."""
    buf = jnp.arange(20, dtype=jnp.float32).reshape(5, 4)
    store = pt.OffloadStore({"z": buf})
    idx = pt.host_put(jnp.asarray([1, 3, 5], jnp.int32))  # 5 = sentinel
    tiles = store.gather_tiles(idx)
    np.testing.assert_array_equal(np.asarray(tiles["z"]),
                                  np.asarray(pt.gather_rows(buf, idx)))
    store.scatter_tiles(idx, {"z": tiles["z"] * -1.0})
    np.testing.assert_array_equal(
        np.asarray(store.buffers["z"]),
        np.asarray(pt.scatter_rows(buf, idx, tiles["z"] * -1.0)))
    assert store.nbytes == int(buf.nbytes)


def test_offload_validation(problem):
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    pol = lambda: make_policy("uniform", M, 0.5, seed=0)
    with pytest.raises(ValueError, match="participant"):
        run_rounds(algo, state, batch, 2, store="offload")
    with pytest.raises(ValueError, match="flat"):
        run_rounds(algo, state, batch, 2, store="offload",
                   participation=pol(), flat=False)
    with pytest.raises(ValueError, match="no chunks"):
        run_rounds(algo, state, batch, 2, store="offload",
                   participation=pol(), chunk_size="auto")
    with pytest.raises(ValueError, match="overlap"):
        run_rounds(algo, state, batch, 2, store="offload",
                   participation=pol(), overlap="scatter")
    with pytest.raises(ValueError, match="unknown aggregate"):
        run_rounds(algo, state, batch, 2, store="active",
                   participation=pol(), aggregate="sparse")
    with pytest.raises(ValueError, match="packed"):
        run_rounds(algo, state, batch, 2, store="dense",
                   participation=pol(), aggregate="packed")


# ------------------------------------------ packed aggregation (fp tol)
@pytest.mark.parametrize("algo_key", ["fedavg", "scaffold"])
def test_packed_matches_dense_fp(problem, algo_key):
    """aggregate='packed' sums the (capacity, N) tile directly — fp
    tolerance vs the bitwise dense layout (~1 ulp: XLA associates the
    m-row and capacity-row reductions differently). SCAFFOLD also
    exercises the extra_mean rider (control-variate delta)."""
    algo, state = _make(problem, algo_key)
    _, batch = problem
    pol = lambda: make_policy("uniform", M, 0.5, seed=3)
    ref = run_rounds(algo, state, batch, ROUNDS, store="active",
                     participation=pol())
    res = run_rounds(algo, state, batch, ROUNDS, store="active",
                     aggregate="packed", participation=pol())
    assert res.rounds_run == ref.rounds_run
    for key in ref.state:
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7),
            res.state[key], ref.state[key])
    np.testing.assert_allclose(res.history["f_xbar"],
                               ref.history["f_xbar"], rtol=1e-5)


def test_packed_weighted_matches_dense_fp(problem):
    """The staleness-weighted packed sum gathers the dense (m,) weights
    by real row ids — fp-equal to the dense weighted aggregate."""
    algo, state = _make(problem, "fedavg")
    _, batch = problem
    clk = lambda: ComputeClock(M, 1.0 + (np.arange(M) % 3))
    ref = run_rounds(algo, state, batch, ROUNDS, store="active", clock=clk(),
                     max_staleness=3, stale_weighting="poly", stale_decay=0.5)
    res = run_rounds(algo, state, batch, ROUNDS, store="active",
                     aggregate="packed", clock=clk(), max_staleness=3,
                     stale_weighting="poly", stale_decay=0.5)
    for key in ref.state:
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7),
            res.state[key], ref.state[key])


def test_packed_offload_matches_packed_active_bitwise(problem):
    """The two new modes compose: offload+packed is bitwise
    active+packed (the store moves data, the aggregate changes math —
    independent axes)."""
    algo, state = _make(problem, "scaffold")
    _, batch = problem
    pol = lambda: make_policy("uniform", M, 0.5, seed=3)
    ref = run_rounds(algo, state, batch, ROUNDS, store="active",
                     aggregate="packed", participation=pol())
    res = run_rounds(algo, state, batch, ROUNDS, store="offload",
                     aggregate="packed", participation=pol())
    _assert_offload_equiv(res, ref)


_SHARDED_PACKED_SCRIPT = textwrap.dedent(
    """
    import jax, jax.numpy as jnp, numpy as np
    from hlo_guard import assert_barrier_round, program_text
    from repro.config import FedConfig
    from repro.core import engine, make_algorithm, make_policy
    from repro.data import linreg_noniid
    from repro.launch.mesh import make_host_mesh
    from repro.models import LeastSquares
    from repro.utils import pytree as pt

    m, n, d = 8, 24, 320
    batch = {k: jnp.asarray(v) for k, v in linreg_noniid(0, d, n, m).items()}
    model = LeastSquares(n)
    mesh = make_host_mesh(data=8)

    fed = FedConfig(algorithm="scaffold", num_clients=m, k0=3, lr=0.01)
    algo = make_algorithm(fed, model.loss, model=model)
    s0 = algo.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1),
                   init_batch=batch)
    spec = pt.ravel_spec(s0["x"])
    s0f = engine.flatten_state(algo, s0, spec)
    cap = make_policy("uniform", m, 0.5).active_capacity
    st, b = engine.shard_inputs(algo, s0f, batch, mesh)
    mask = jnp.ones((m,), bool)

    def hlo(aggregate):
        rf = engine.make_round_fn(algo, mesh, masked=True, flat_spec=spec,
                                  active_capacity=cap, aggregate=aggregate)
        return jax.jit(rf).lower(st, b, mask).compile().as_text()

    txt = hlo("packed")
    assert_barrier_round(txt, "scaffold-packed")
    # under a mesh the sharded branch is ALREADY packed inside its one
    # psum: the flag must leave the lowered program unchanged (up to the
    # source locations of the two call sites)
    assert program_text(txt) == program_text(hlo("dense")), (
        "packed flag changed the sharded program")
    print("PACKED_SHARDED_OK one model-size all-reduce")
    """
)


def test_packed_sharded_one_all_reduce():
    """The sharded packed round keeps eq. (11) as exactly ONE model-size
    all-reduce, and the packed flag is a program-level no-op under a
    mesh (the sharded branch already sums the packed tile)."""
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_PACKED_SCRIPT],
        env=fake_device_env(8), capture_output=True, text=True, timeout=900,
    )
    assert "PACKED_SHARDED_OK" in out.stdout, out.stdout + out.stderr
