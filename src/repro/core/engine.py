"""Scan-compiled, client-sharded round engine.

Every federated algorithm in this repo (FedGiA + the four §V.D baselines)
exposes the same `FederatedAlgorithm` protocol (core/api.py): a pure
`round(state, batch) -> (state, metrics)`. The legacy driver dispatched one
jitted round per Python iteration and synced a metric scalar to the host
every round — on small problems the wall-clock is dominated by dispatch,
not math. This engine removes both costs without changing a single number
(tests/test_engine.py asserts bitwise-faithful fp32 equivalence):

  * **scan path** — `run_rounds` compiles CHUNKS of rounds into a single
    `jax.lax.scan` inside one jit with the carry donated. Per-round metrics
    are stacked device-side; the tolerance check of the paper's stopping
    rule (eq. 35) runs INSIDE the scan: a `lax.cond` freezes the carry once
    the tolerance is met, so finished rounds cost (almost) nothing and the
    host syncs ONE boolean per chunk instead of one float per round.
  * **client-sharded path** — `mesh=` places the leading client axis of the
    client state (`algo.client_state_keys`) and the batch over a mesh axis
    with `shard_map`. Cross-client reductions inside `round` go through
    `api.client_mean` & friends, so eq. (11)'s aggregation lowers to the
    round's ONE `psum` — exactly the paper's single all-reduce per round.
  * **legacy path** — `scan=False` keeps the per-round Python loop
    (`--no-scan` in the launchers) for debugging.
  * **partial participation** — `participation=` takes a
    `core.selection.ParticipationPolicy`; its state rides in the scan
    carry, a fresh (m,) mask is drawn on device every round and handed to
    `round(state, batch, mask)` (auto-sliced per shard on the sharded
    path, where the masked aggregation still lowers to ONE psum). See
    docs/engine.md.
  * **async / overlapped rounds** — `async_rounds=True` reinterprets the
    participation mask as an ARRIVAL process: a `StaleXbar` buffer
    (core/api.py) rides in the scan carry next to the policy state, and a
    client that has not arrived for s rounds runs its branch against the
    stale anchor x̄^(t-s), s <= `max_staleness` (bounded by a forced
    server sync). `max_staleness=0` is bitwise identical to the masked
    synchronous engine on every path. See docs/async.md.
  * **wall-clock rounds** — `clock=` takes a `core.clock.ComputeClock`
    (per-client compute/communication time model) and makes the arrival
    mask EVENT-DRIVEN: the clock's state (in-flight finish times +
    simulated server time) rides in the scan carry and each round's mask
    is derived from simulated client finish times instead of sampled
    from a policy. Rounds report the simulated wall-clock (`sim_time`)
    alongside CR, and `stale_weighting=` turns eq. (11) into the
    staleness-aware weighted mean (`api.stale_weights`) — uniform
    weighting is today's unweighted path, bitwise. See docs/async.md.

  * **flat-buffer rounds** — `flat=True` (default) ravels the model-shaped
    state ONCE at the `run_rounds` boundary (`utils.pytree.ravel_spec`):
    client state becomes one contiguous lane-padded (m, N) buffer per key,
    anchors (N,) vectors, and the rounds dispatch to `algo.round_flat`.
    Eq. (11) is a mean over a single array (under sharding: the round's
    ONE model-size all-reduce), the stale anchor buffer is one (m, N)
    array, and FedGiA's ADMM/GD branch is one fused elementwise pass
    (the batched Pallas `kernels/fedgia_update` kernel on TPU). The
    pytree layout is reconstructed only at the gradient/metric
    boundaries and at return; `flat=False` (`--no-flat`) keeps the
    per-leaf pytree rounds, bitwise-equal on a single device
    (tests/test_flat.py). See docs/engine.md#flat-buffer-round-state.

  * **overlapped collectives** — `overlap="scatter"` splits eq. (11)'s
    psum into a round-END `psum_scatter` into a column-sharded carry slot
    (`state["ovl_shard"]`) plus a round-TOP `all_gather` of the consensus
    back out, so the local compute between them hides the wire; the
    client axis may span pods (`client_axis=("pod", "data")`) and the
    Pallas hot path can donate its buffers (`donate_kernel=`). See
    docs/engine.md#overlapped-collectives.

Scan-carry layout (donated between chunks):

    (state, policy_state, clock_state, stale, done, rounds_run)

where `state` is the algorithm state dict, `policy_state` the
participation policy's pytree (() when participation is None),
`clock_state` the wall-clock simulation state (() when clock is None),
`stale` the async `StaleXbar` (() when async_rounds is False), `done`
the eq.-35 stop flag and `rounds_run` an int32 round counter. The legacy
loop threads the same tuple through its per-round jitted step, which is
why scan == legacy holds exactly for every feature combination.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import json
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import checkpoint as ckpt_io
from repro.core import api, compress
from repro.utils import pytree as pt


@dataclasses.dataclass
class RoundResult:
    """Outcome of `run_rounds`: final state + stacked per-round metrics."""

    state: Any
    history: Dict[str, np.ndarray]  # each (rounds_run,), trimmed at early stop
    rounds_run: int
    stopped_early: bool
    wall_s: float
    # Path-specific diagnostics that are not per-round metrics. The
    # host-offloaded store reports `device_peak_bytes` (XLA
    # memory_analysis of the compiled tile round, when the backend
    # exposes it), `host_resident_bytes` (the buffers that left the
    # device) and where they went (`host_memory_kind`,
    # `tile_memory_kind`, `host_placement_reason`; see
    # `_run_offload_loop`). Empty for the dense/active paths.
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------- sharding
def _full_spec(leading: Optional[str], ndim: int) -> P:
    return P(leading, *([None] * (ndim - 1))) if ndim else P()


def _state_specs(algo, state_like, axis):
    """Per-leaf PartitionSpecs: client-stacked top-level keys on `axis`
    (`axis` may be a compound tuple, e.g. ``('pod', 'data')``). The
    overlap carry slot ``"ovl_shard"`` is the one exception: it holds the
    reduce-scattered consensus CHUNKS, sharded over COLUMNS, not over a
    leading client axis — spec ``P(None, axis)``."""
    client_keys = set(getattr(algo, "client_state_keys", ()))
    specs = {
        k: jax.tree.map(
            lambda l, kk=k: _full_spec(axis if kk in client_keys else None, l.ndim),
            v,
        )
        for k, v in state_like.items()
    }
    if "ovl_shard" in specs:
        specs["ovl_shard"] = P(None, axis)
    return specs


def _batch_specs(batch_like, axis):
    return jax.tree.map(lambda l: _full_spec(axis, l.ndim), batch_like)


def _client_axes(client_axis) -> tuple:
    """Normalise `client_axis` to a tuple of mesh axis names: the client
    dimension may span one axis (``"data"``) or a compound of several
    (``("pod", "data")`` — pod-spanning client sharding)."""
    return client_axis if isinstance(client_axis, tuple) else (client_axis,)


def _client_shards(mesh, client_axis) -> int:
    """Total client shards = product of the client axes' mesh sizes."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    shards = 1
    for a in _client_axes(client_axis):
        if a not in sizes:
            raise ValueError(f"mesh has no axis {a!r}: {mesh.axis_names}")
        shards *= sizes[a]
    return shards


def flatten_state(algo, state, spec):
    """Ravel the algorithm state's model-shaped entries into flat buffers:
    `algo.flat_global_keys` -> (N,) vectors, `algo.flat_client_keys` ->
    one (m, N) buffer each (`spec` = `pt.ravel_spec(state["x"])`). Done
    ONCE at the `run_rounds` boundary; everything else (rng, scalars,
    gram factors) passes through untouched."""
    out = dict(state)
    for k in getattr(algo, "flat_global_keys", ()):
        if k in out:
            out[k] = spec.ravel(out[k])
    for k in getattr(algo, "flat_client_keys", ()):
        if k in out:
            out[k] = spec.ravel_stacked(out[k])
    return out


def unflatten_state(algo, state, spec):
    """Inverse of `flatten_state` — the return boundary: callers always
    see the pytree state layout, whichever path ran the rounds."""
    out = dict(state)
    for k in getattr(algo, "flat_global_keys", ()):
        if k in out:
            out[k] = spec.unravel(out[k])
    for k in getattr(algo, "flat_client_keys", ()):
        if k in out:
            out[k] = spec.unravel_stacked(out[k])
    return out


def make_round_fn(algo, mesh=None, client_axis="data",
                  masked: bool = False, stale: bool = False,
                  flat_spec=None, active_capacity: Optional[int] = None,
                  compressor=None, overlap: str = "off",
                  donate_kernel: bool = False, aggregate: str = "dense",
                  faults=None, screening=None):
    """`algo.round`, optionally wrapped in `shard_map` over the client axis.

    `masked=True` returns a `(state, batch, mask) -> (state, metrics)`
    callable: the engine-drawn (m,) participation mask enters `shard_map`
    with spec `P(client_axis)`, so each shard's round body receives its
    own contiguous (m_local,) block — algorithms never re-slice it.

    `stale=True` (implies masked) additionally threads the async
    `StaleXbar` state: the callable is `(state, batch, mask, stale) ->
    (state, stale, metrics)`. Every StaleXbar leaf carries the leading
    client axis, so it enters and leaves `shard_map` with per-client
    specs — the stale-anchor selects are shard-local and the round keeps
    eq. (11) as its ONE model-size psum.

    `flat_spec` (a `pt.RavelSpec`) selects the FLAT round: the callable
    has the same signature but `state` carries the raveled (m, N) /
    (N,) buffers (`flatten_state`) and dispatch goes to
    `algo.round_flat(state, batch, spec, ...)` instead of `algo.round`.

    `active_capacity` (with `flat_spec`, implies masked) selects the
    ACTIVE-SET round (`run_rounds(store="active")`): the round's (m,)
    mask is packed into a `pt.ActiveSet` of that static capacity INSIDE
    the round body and dispatch goes to `algo.round_flat_active`. The
    callable's signature is unchanged — the pack happens downstream of
    the mask draw, so the scan carry, the chunked drivers and the legacy
    loop are identical between stores. Under a mesh the pack runs inside
    `shard_map` on the shard-local (m_local,) mask, so the capacity is
    clamped to m_local (a shard can never host more participants than it
    has clients).

    `compressor` (a `core.compress.Compressor`, flat rounds only) is
    threaded into `round_flat`/`round_flat_active` as a keyword: each
    client's eq.-(11) contribution is encoded+decoded LOCALLY before it
    enters the round's aggregation (decompress-before-reduce), so the
    sharded round still lowers to its ONE model-size all-reduce. None
    keeps the uncompressed round — structurally, not just numerically.

    `client_axis` may be a single mesh axis name or a compound tuple
    (``("pod", "data")``): client state and batch shard over the product
    of the named axes and every cross-client collective runs over the
    compound axis — pod-spanning client sharding with no change to the
    round bodies.

    `overlap="scatter"` (flat rounds only) validates the split-collective
    round here: the round body reads the previous round's consensus from
    the ``state["ovl_shard"]`` carry slot (`api.flat_overlap_consensus`'s
    all-gather at the round TOP) and writes this round's reduction back
    with `api.flat_overlap_aggregate`'s reduce-scatter at the round END —
    `run_rounds` creates/finalises the slot. Under a mesh the lane-padded
    buffer must divide over the client shards (the reduce-scatter chunks
    columns). ``"off"`` keeps the one-psum barrier round, bitwise.

    `donate_kernel=True` threads Pallas buffer donation into the flat
    rounds (`FedGiA.round_flat(donate_kernel=True)`): the kernel aliases
    its (m, N) state inputs to its outputs (`input_output_aliases`), so
    the hot-path update is in-place end-to-end under the donated scan
    carry. Ignored by algorithms without a kernel path.

    `faults` (a `core.faults.FaultModel`) / `screening`
    (`core.faults.Screening`) thread the fault-injection and defensive
    screening stage into the flat rounds (`api.harden_upload[_active]`
    between the codec and the aggregation): faults corrupt the decoded
    uploads on device from a stateless per-(round, client) key stream —
    identical across scan/legacy, stores and shardings — and screening
    folds a per-row finite check + norm clip into the participation mask
    BEFORE eq. (11)'s psum, so the sharded round keeps its one
    model-size collective set. None/None keeps the un-hardened round —
    structurally, not just numerically.

    `aggregate="packed"` (active rounds only) opts eq. (11) into the
    fp-tolerance packed aggregation: the unsharded round sums the
    (capacity, N) tile directly instead of scattering it back to the
    dense (m, N) layout first (`ActiveSet.packed`; ~1 ulp from the
    bitwise dense default). Under a mesh the flag is a no-op — the
    sharded branch already keeps packed O(capacity) sums inside the
    round's one psum, so the lowered program is unchanged.
    """
    if overlap not in ("off", "scatter"):
        raise ValueError(f"unknown overlap {overlap!r}: ('off', 'scatter')")
    if overlap == "scatter" and flat_spec is None:
        raise ValueError(
            "overlap='scatter' splits the flat comm buffer's collective — "
            "it requires the flat round path (flat=True on an algorithm "
            "providing round_flat; drop --no-flat)")
    if aggregate not in ("dense", "packed"):
        raise ValueError(
            f"unknown aggregate {aggregate!r}: ('dense', 'packed')")
    if aggregate == "packed" and active_capacity is None:
        raise ValueError(
            "aggregate='packed' sums the packed participant tile — it "
            "requires the active-set round (store='active' or 'offload')")
    if flat_spec is not None and active_capacity is not None:
        cap = active_capacity
        if mesh is not None:
            cap = min(cap,
                      algo.fed.num_clients // _client_shards(mesh, client_axis))
        packed = aggregate == "packed"

        def base_round(state, batch, mask, *extra):
            aset = pt.make_active_set(mask, cap, packed=packed)
            return algo.round_flat_active(state, batch, flat_spec, aset,
                                          *extra, compressor=compressor,
                                          donate_kernel=donate_kernel,
                                          faults=faults, screening=screening)
    elif flat_spec is not None:
        base_round = lambda state, batch, *extra: algo.round_flat(
            state, batch, flat_spec, *extra, compressor=compressor,
            donate_kernel=donate_kernel, faults=faults, screening=screening)
    else:
        if compressor is not None:
            raise ValueError(
                "compression operates on the flat (m, N) comm buffer — "
                "the pytree round path (flat=False) does not support it")
        if faults is not None or screening is not None:
            raise ValueError(
                "faults/screening operate on the flat (m, N) comm buffer — "
                "the pytree round path (flat=False) does not support them")
        base_round = algo.round
    if mesh is None:
        if stale:
            return lambda state, batch, mask, sl: base_round(
                state, batch, mask, sl)
        if masked:
            return lambda state, batch, mask: base_round(state, batch, mask)
        return base_round
    shards = _client_shards(mesh, client_axis)
    m = algo.fed.num_clients
    if m % shards != 0:
        raise ValueError(f"num_clients={m} not divisible by {shards} shards")
    if overlap == "scatter" and flat_spec.padded_size % shards != 0:
        raise ValueError(
            f"overlap='scatter' reduce-scatters the lane-padded buffer "
            f"column-wise: padded_size={flat_spec.padded_size} must divide "
            f"over {shards} client shards")

    client_spec = lambda tree: jax.tree.map(
        lambda l: _full_spec(client_axis, l.ndim), tree
    )

    def body(state, batch, *extra):
        # context makes api.client_mean/... collective over `client_axis`
        with api.client_sharding(client_axis, shards):
            return base_round(state, batch, *extra)

    def sharded_round(state, batch, *extra):
        abs_out = jax.eval_shape(base_round, state, batch, *extra)
        in_specs = (_state_specs(algo, state, client_axis),
                    _batch_specs(batch, client_axis))
        if masked or stale:
            in_specs = in_specs + (P(client_axis),)  # the (m,) mask
        if stale:
            in_specs = in_specs + (client_spec(extra[1]),)
            abs_state, abs_stale, abs_met = abs_out
            out_specs = (_state_specs(algo, abs_state, client_axis),
                         client_spec(abs_stale),
                         jax.tree.map(lambda l: _full_spec(None, l.ndim),
                                      abs_met))
        else:
            abs_state, abs_met = abs_out
            out_specs = (_state_specs(algo, abs_state, client_axis),
                         jax.tree.map(lambda l: _full_spec(None, l.ndim),
                                      abs_met))
        return jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )(state, batch, *extra)

    return sharded_round


def shard_inputs(algo, state, batch, mesh, client_axis: str = "data"):
    """Place client-stacked leaves over `client_axis`, replicate the rest."""
    sspec = _state_specs(algo, state, client_axis)
    bspec = _batch_specs(batch, client_axis)
    put = lambda tree, spec: jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), tree, spec
    )
    return (
        {k: put(v, sspec[k]) for k, v in state.items()},
        put(batch, bspec),
    )


def _device_buffers(leaf) -> set:
    return {shard.data.unsafe_buffer_pointer()
            for shard in leaf.addressable_shards}


def _release(tree, keep) -> None:
    """Delete the device buffers of `tree`'s arrays that no array of
    `keep` shares (a placement that moves nothing returns a new array
    over the same buffers)."""
    arrays = lambda t: [l for l in jax.tree.leaves(t)
                        if isinstance(l, jax.Array) and not l.is_deleted()]
    held = set().union(*[_device_buffers(l) for l in arrays(keep)])
    for leaf in arrays(tree):
        if not _device_buffers(leaf) & held:
            leaf.delete()


# ------------------------------------------------------------------ driver
AUTO_CHUNK_CANDIDATES = (8, 32, 128)


@functools.partial(jax.profiler.annotate_function, name="run_rounds")
def run_rounds(
    algo,
    state,
    batch,
    num_rounds: int,
    *,
    tol: float = 0.0,
    tol_metric: str = "grad_sq_norm",
    scan: bool = True,
    chunk_size=0,
    donate: Optional[bool] = None,
    mesh=None,
    client_axis: str = "data",
    participation=None,
    async_rounds: bool = False,
    max_staleness: int = 0,
    clock=None,
    stale_weighting: str = "uniform",
    stale_decay: float = 1.0,
    flat: bool = True,
    store: str = "dense",
    aggregate: str = "dense",
    compression=None,
    error_feedback: bool = False,
    topk_frac: float = 0.1,
    overlap: str = "off",
    donate_kernel: Optional[bool] = None,
    faults=None,
    screening=None,
    quorum: int = 0,
    watchdog: bool = False,
    watchdog_patience: int = 3,
    watchdog_factor: float = 2.0,
    checkpoint_every: int = 0,
    checkpoint_dir: Optional[str] = None,
    resume: bool = False,
    consume: bool = False,
) -> RoundResult:
    """Run up to `num_rounds` communication rounds of `algo`.

    tol > 0 enables the paper's stopping rule (eq. 35): stop after the
    first round with metrics[tol_metric] < tol (that round counts as run).
    chunk_size=0 picks a default: the whole run when tol is off, else 32
    rounds between (single-boolean) host checks. chunk_size="auto"
    autotunes on the live run (unsharded scan path only — the sharded
    path has no AOT warm-up, so candidate timings would measure
    compilation): the first chunks execute the AOT-pre-compiled
    `AUTO_CHUNK_CANDIDATES` lengths in turn, each is timed, and the
    fastest per-round candidate drives the remainder. The rounds executed
    are identical whatever the timings, so with tol <= 0 results are
    bitwise deterministic; with tol > 0 only the stop GRANULARITY (which
    is already chunk-dependent) can differ between machines. The tuner
    composes with store="active": the tile gather/scatter runs inside
    every round whatever the chunk length, so candidate timings stay
    comparable and the winning chunk is store-independent
    (tests/test_store.py pins auto-chunk == fixed-chunk under the active
    store).

    flat=True (default) runs the FLAT round path when the algorithm
    provides it (`round_flat`): the model-shaped state is raveled ONCE
    into contiguous lane-padded buffers (`utils.pytree.ravel_spec`) —
    client state one (m, N) array, anchors (N,) — the scan/legacy/sharded
    drivers carry those buffers, and the pytree layout is reconstructed
    only at the gradient/metric boundaries inside the round and at this
    function's return. Eq. (11) becomes one contiguous model-size
    reduction (under sharding: the round's single model-size all-reduce,
    HLO-asserted in tests/test_flat.py) and FedGiA's branch update a
    single fused elementwise pass (the batched Pallas kernel on TPU).
    `flat=False` (`--no-flat` in the launchers) keeps the per-leaf pytree
    rounds; both paths produce bitwise-identical results on every
    single-device configuration (fp-tolerance where the Pallas kernel or
    the sharded fused psum is involved — tests/test_flat.py).

    participation: a `core.selection.ParticipationPolicy`. Its state rides
    in the scan carry and a fresh (m,) mask is drawn ON DEVICE each round
    and passed to `round(state, batch, mask)` (sliced per shard on the
    client-sharded path). None keeps the legacy in-algorithm behaviour.

    async_rounds: overlapped (stale-x̄) rounds. Requires an arrival
    process — either a participation policy (its mask becomes WHO
    uploads/downloads this round) or a `clock`. An `api.StaleXbar` buffer
    rides in the scan carry: each client anchors its branch on the x̄ it
    last downloaded, at most `max_staleness` rounds old (over-stale
    clients are force-synced first). The history gains a per-round
    `staleness` (m,) vector and `staleness_max` scalar.
    `max_staleness=0` is bitwise identical to the synchronous masked
    engine (tests/test_async.py pins this for all five algorithms).

    clock: a `core.clock.ComputeClock` — wall-clock event-driven rounds
    (implies async_rounds; mutually exclusive with `participation`). The
    clock's state rides in the scan carry and each round's arrival mask
    is DERIVED from simulated client finish times; the history gains the
    per-round simulated wall-clock `sim_time`. With identical client
    speeds every client arrives every round — bitwise identical to a
    full-participation arrival policy (tests/test_wallclock.py).

    stale_weighting/stale_decay: staleness-aware aggregation schedule for
    eq. (11) (`api.stale_weights`): "uniform" (default, today's
    unweighted path — bitwise), "poly" ((1+s)^-decay) or "exp"
    (e^(-decay*s)). Requires async_rounds (or clock).

    store: client-state execution strategy for the flat path. "dense"
    (default) keeps every round's working set (m, N) — trajectories and
    gradients are computed for all m clients and non-participants are
    masked out, the only shape-stable formulation when every client runs
    a branch (FedGiA's GD rewrite). "active" packs the round down to the
    participants: the resident (m, N) client buffers stay in the donated
    scan carry, but each round GATHERS a (capacity, N) tile of the
    selected clients (capacity = `participation.active_capacity`, or m
    under a clock), runs the algorithm's `round_flat_active` on the
    tile, and SCATTERS per-client state back — the round's broadcasts,
    trajectories and gradient evaluations shrink from m rows to
    capacity, which is what makes m=10^6, alpha=10^-4 rounds tractable
    (benchmarks/engine_bench.py `active_1m`). States are bitwise equal
    between stores (tests/test_store.py); loss/gradient diagnostics
    become PARTICIPANT means — the server cannot observe clients it
    never contacted. Requires flat=True and a participation policy or
    clock; FedGiA declares `active_tile="population"` (every client is
    rewritten every round by eqs. 15-17) and falls back to the dense
    round internally. "offload" moves the resident (m, N) client
    buffers (and the batch + StaleXbar anchor) into HOST memory
    (pinned host memory where the backend supports computing on it,
    else the CPU device — `pt.host_placement`): each round gathers only
    the (capacity, N) participant tiles to the device, runs
    `round_flat_active` in tile mode (`ActiveSet.tile_state`), and
    scatters the updated tiles back host-side — double-buffered (the
    next round's mask/batch tile are staged while the current round's
    device compute is in flight) with tile donation off-CPU, so m is
    bounded by host RAM instead of device HBM. Bitwise equal to
    store="active" (host gather/scatter is pure data movement —
    tests/test_store.py); single-device only (no mesh/overlap; the
    scan flag is accepted but the loop is host-driven, so
    chunk_size="auto" is rejected). FedGiA's population tile shuttles
    the full buffers each round instead (residency, not per-round
    traffic, is what moves off-device). `RoundResult.extras` reports
    `device_peak_bytes` / `host_resident_bytes`. See
    docs/engine.md#host-offloaded-store and docs/scaling.md.

    aggregate: eq. (11) aggregation layout for active/offload rounds.
    "dense" (default) scatters the participant tile back to the dense
    (m, N) layout before reducing — bitwise the dense store. "packed"
    sums the (capacity, N) tile directly — O(capacity·N) and no dense
    (m, N) aggregation temp, at fp tolerance (~1 ulp: XLA associates
    the two reduction shapes differently). Under a mesh the sharded
    branch is already packed inside its one psum, so the flag leaves
    the lowered program unchanged. See
    docs/engine.md#packed-aggregation.

    compression: uplink codec for the flat comm buffer — "none"/None,
    "bf16", "int8", "topk" or a `core.compress.Compressor` instance.
    Each client's eq.-(11) contribution is encoded+decoded LOCALLY
    before it enters the round's aggregation (decompress-before-reduce),
    so the sharded round keeps its ONE model-size all-reduce and `none`
    is BITWISE the uncompressed engine (the identity codec without
    error feedback resolves to the very same lowered program —
    tests/test_compress.py pins this for all five algorithms).
    Requires the flat round path.

    error_feedback: per-client error-feedback residuals (EF): each
    client uploads C(contrib + ef) and keeps ef' = (contrib + ef) -
    C(contrib + ef) in one extra (m, N) flat buffer `state["ef"]`
    riding the scan carry like any other flat client key (dense and
    active stores carry it for free; non-participants' residuals are
    frozen). Requires a lossy compression codec.

    topk_frac: fraction of lanes the "topk" codec keeps (largest-|·|
    per client), 0 < topk_frac <= 1.

    With a byte-accurate clock (`clock.bandwidth_bps` set) the codec's
    exact wire size prices the simulated communication time: the engine
    installs `compress.uplink_bytes`/`downlink_bytes` of the model on
    the clock (`ComputeClock.with_wire`) and the history gains per-round
    `bytes_up`/`bytes_down` totals (arrived clients × per-client wire).

    overlap: ``"off"`` (default) keeps the barrier round — eq. (11) as
    one fused model-size psum, bitwise the PR-5 program. ``"scatter"``
    splits it: each round ENDS with a `psum_scatter` of the stacked
    contribution rows (`api.flat_overlap_aggregate`) into a
    column-sharded carry slot ``state["ovl_shard"]``, and the NEXT round
    STARTS by all-gathering the consensus back
    (`api.flat_overlap_consensus`) — the local compute between the two
    halves hides the wire. The slot is a pure carry-layout change: its
    row 0 is exactly the mean the barrier round would have computed, so
    results are bitwise the barrier engine unsharded (fp tolerance under
    a mesh, where the reduce-scatter reassociates the sum) — the only
    semantic shift is FedGiA's uplink-compression timing (the z upload is
    encoded at round end instead of the next round's top; lossless runs
    are unaffected, see docs/engine.md#overlapped-collectives). At the
    return boundary the engine folds the slot back into the state
    (``algo.overlap_finalize`` when defined, else ``x = slot[0]``), so
    callers see the ordinary state layout. Requires the flat round path;
    with a clock, round durations become ``max(compute, comm)`` instead
    of ``compute + comm`` (`ComputeClock.with_overlap`). Under a mesh the
    lane-padded buffer must divide over the client shards.

    faults / screening: fault-tolerant rounds (docs/faults.md). `faults`
    (a `core.faults.FaultModel`) corrupts the decoded uploads ON DEVICE
    just before eq. (11) — crash/drop, NaN/Inf payloads, update
    explosions, stale replays — from a stateless per-(round, client) key
    stream, so the injected stream is identical across scan/legacy, all
    three stores and shardings, and across checkpoint resume (no fault
    rng rides the carry). `screening` (`core.faults.Screening`) is the
    defense: a per-row finite check + optional norm clip folded into the
    participation mask BEFORE the psum — the screened mask and clip
    scale are riders on the round's ONE model-size collective set
    (tests/test_faults.py HLO-asserts {1 AR} / {1 RS, 1 AG}). The
    history gains a per-round `screened` count. Flat rounds only.

    quorum: minimum accepted-upload count for a round to COMMIT. A round
    whose screened/selected count falls below it becomes a recorded
    no-op: every state entry except the rng and the round counter
    reverts (x̄ is carried, partial aggregation is never applied — the
    biased mean of eq. (11) over too few clients is worse than waiting),
    and the history records `degraded=True` for that round. quorum=0
    (default) keeps today's always-commit rounds structurally unchanged.
    Required >= 1 under a deadline clock (`ComputeClock(deadline_s=)`),
    whose rounds can see zero arrivals.

    watchdog: carry-resident divergence watchdog. Tracks the best f̄
    seen (`f_xbar`) plus a full state snapshot in the scan carry; after
    `watchdog_patience` consecutive non-degraded rounds with
    f̄ > `watchdog_factor` × best (NaN counts as diverged), the state
    rolls back to the snapshot (rng/round keep advancing — the run does
    not relive the same faults) and the history records
    `rollback=True`. Degraded rounds never advance the patience counter.
    The snapshot doubles the carry, so the watchdog is opt-in; with
    store="offload" it is rejected (it would double host residency).

    checkpoint_every / checkpoint_dir / resume: bitwise checkpoint +
    resume (docs/faults.md#checkpointing). Every `checkpoint_every`
    rounds the FULL carry — state (incl. ef / fault_prev / overlap
    slot), policy/clock state, StaleXbar, watchdog slot, rng, stop flag
    — plus the history so far is written through
    `checkpoint/checkpoint.py` (atomic npz). `resume=True` restores the
    newest checkpoint under `checkpoint_dir` (a fresh start when none
    exists) and the resumed run's history and final state are BITWISE
    the uninterrupted run's. Checkpoints embed a config fingerprint;
    resuming under a different round-semantics configuration raises
    (num_rounds is excluded — extending a finished run is the point).
    Supported on the chunked scan driver and the host-driven offload
    loop; rejected with chunk_size="auto" and under a mesh.

    consume: the call takes the caller's state over: once the engine
    holds its own (flat) buffers the caller's are deleted, and the carry
    is donated without the protective copy `donate` makes otherwise.
    Leaves the engine keeps as they are (scalars, the rng) are donated
    with the carry. A state of several GB a chip then needs one copy on
    the device at a time, not three (caller's, flat, copy). The caller
    must not read the state it passed in again.

    donate_kernel: donate the flat (m, N) state buffers into the Pallas
    `fedgia_update` kernel (`input_output_aliases` + XLA donation), so
    the collapsed diagonal-H update writes in place — no extra (m, N)
    temp in `memory_analysis()` (tests/test_kernels.py). None (default)
    resolves by backend like `donate`: enabled off-CPU, disabled on CPU
    (CPU XLA cannot alias, and the CPU Pallas path is interpret-only).
    Ignored by algorithms without a kernel path.

    Host spans (`jax.profiler.TraceAnnotation`, on the profiler's clock
    with the device's ops): `run_rounds` covers the call;
    `run_rounds.prepare` the checks, ravel, state copy and carry up to
    the first chunk program; `run_rounds.lower` and `run_rounds.compile`
    each AOT-compiled chunk length; `run_rounds.fetch` the history and
    state brought back after the last chunk. The chunk loop, where the
    device works, lies outside the phase spans. On the legacy and
    offload loops only `run_rounds` and `run_rounds.prepare` are
    recorded.
    """
    with contextlib.ExitStack() as prepare:
        prepare.enter_context(
            jax.profiler.TraceAnnotation("run_rounds.prepare"))
        if num_rounds <= 0:
            return RoundResult(state, {}, 0, False, 0.0)
        auto_chunk = isinstance(chunk_size, str)
        if auto_chunk:
            if chunk_size != "auto":
                raise ValueError(
                    f"chunk_size must be an int or 'auto', got {chunk_size!r}")
            if not scan:
                raise ValueError(
                    "chunk_size='auto' tunes the scan chunk length — the "
                    "legacy per-round loop (scan=False) has no chunks")
            if mesh is not None:
                # chunks compile lazily under a mesh (GSPMD may re-place carry
                # leaves between chunks, so there is no AOT warm-up) — the
                # candidate timings would measure compilation, not rounds
                raise ValueError(
                    "chunk_size='auto' needs AOT-precompiled candidates to "
                    "time execution, which the sharded path does not have — "
                    "pass a fixed chunk_size under a mesh")
        if clock is not None:
            if participation is not None:
                raise ValueError(
                    "clock= and participation= are mutually exclusive: the "
                    "clock DERIVES the arrival mask from simulated finish "
                    "times (core/clock.py), a policy samples it"
                )
            if clock.m != algo.fed.num_clients:
                raise ValueError(
                    f"clock models {clock.m} clients, algorithm has "
                    f"{algo.fed.num_clients}"
                )
            async_rounds = True  # a clock IS an arrival process
        if stale_weighting not in api.STALE_WEIGHTINGS:
            raise ValueError(
                f"unknown stale_weighting {stale_weighting!r}: "
                f"{api.STALE_WEIGHTINGS}"
            )
        if stale_weighting != "uniform" and not async_rounds:
            raise ValueError(
                "stale_weighting only applies to async rounds — pass "
                "async_rounds=True (with a participation policy) or clock="
            )
        masked = participation is not None or clock is not None
        if async_rounds:
            if not masked:
                raise ValueError(
                    "async_rounds requires an arrival process — a "
                    "participation policy (e.g. "
                    "selection.AvailabilityParticipation) or a clock "
                    "(core.clock.ComputeClock)"
                )
            if max_staleness < 0:
                raise ValueError(
                    f"max_staleness must be >= 0, got {max_staleness}")
            if "x" not in state:
                raise ValueError(
                    "async_rounds needs the global anchor under state['x'] "
                    "(FederatedAlgorithm state contract)"
                )
        flat = flat and hasattr(algo, "round_flat")
        if overlap not in ("off", "scatter"):
            raise ValueError(
                f"unknown overlap {overlap!r}: ('off', 'scatter')")
        if overlap == "scatter" and not flat:
            raise ValueError(
                "overlap='scatter' splits the flat comm buffer's collective "
                "— it requires the flat round path (flat=True on an "
                "algorithm providing round_flat; drop --no-flat)")
        if donate_kernel is None:
            # same backend rule as carry donation: CPU XLA cannot alias
            # buffers (and the CPU Pallas path is interpret-only)
            donate_kernel = jax.default_backend() != "cpu"
        if store not in ("dense", "active", "offload"):
            raise ValueError(
                f"unknown store {store!r}: ('dense', 'active', 'offload')")
        active_capacity = None
        if store in ("active", "offload"):
            if not flat:
                raise ValueError(
                    f"store={store!r} packs the flat (m, N) client buffers — "
                    "it requires the flat round path (flat=True on an "
                    "algorithm providing round_flat; drop --no-flat)"
                )
            if not masked:
                raise ValueError(
                    f"store={store!r} needs a per-round participant set to "
                    "pack the tile from — pass participation= "
                    "(core.selection) or clock= (core.clock)"
                )
            if not hasattr(algo, "round_flat_active"):
                raise ValueError(
                    f"algorithm {getattr(algo, 'name', algo)!r} does not "
                    "implement round_flat_active"
                )
            active_capacity = (algo.fed.num_clients if clock is not None
                               else participation.active_capacity)
        if store == "offload":
            if mesh is not None:
                raise ValueError(
                    "store='offload' is the single-device host/device split "
                    "— under a mesh the resident buffers are already sharded "
                    "over devices; pass store='active' instead"
                )
            if overlap != "off":
                raise ValueError(
                    "store='offload' runs the host-driven tile loop — the "
                    "overlapped-collective carry slot (overlap='scatter') "
                    "does not ride it"
                )
            if auto_chunk:
                raise ValueError(
                    "chunk_size='auto' tunes the scan chunk length — the "
                    "host-driven offload loop (store='offload') has no chunks"
                )
        if aggregate not in ("dense", "packed"):
            raise ValueError(
                f"unknown aggregate {aggregate!r}: ('dense', 'packed')")
        if aggregate == "packed" and store == "dense":
            raise ValueError(
                "aggregate='packed' sums the packed participant tile — it "
                "requires store='active' or store='offload'")
        compressor = compress.as_compressor(
            compression, error_feedback=error_feedback, topk_frac=topk_frac)
        # the clock prices the wire the codec actually produces, even when
        # the identity codec is resolved away below
        wire_comp = compressor
        if compressor is not None and compressor.identity \
                and not compressor.error_feedback:
            # bitwise escape: the identity codec without error feedback IS
            # the uncompressed round — resolve to the same lowered program,
            # not merely the same values
            compressor = None
        if compressor is not None and not flat:
            raise ValueError(
                "compression operates on the flat (m, N) comm buffer — it "
                "requires the flat round path (flat=True on an algorithm "
                "providing round_flat; drop --no-flat)"
            )
        if (faults is not None or screening is not None) and not flat:
            raise ValueError(
                "faults/screening operate on the flat (m, N) comm buffer — "
                "they require the flat round path (flat=True on an algorithm "
                "providing round_flat; drop --no-flat)"
            )
        if faults is not None and faults.num_clients != algo.fed.num_clients:
            raise ValueError(
                f"fault model covers {faults.num_clients} clients, algorithm "
                f"has {algo.fed.num_clients}")
        if quorum:
            if not 0 < quorum <= algo.fed.num_clients:
                raise ValueError(
                    f"quorum must be in [0, m={algo.fed.num_clients}], "
                    f"got {quorum}")
            if not masked and faults is None and screening is None:
                raise ValueError(
                    "quorum needs a source of non-arrival to guard against "
                    "— pass participation=, clock=, faults= or screening="
                )
        deadline_clock = (clock is not None
                          and getattr(clock, "deadline_s", None) is not None)
        if deadline_clock and quorum < 1:
            raise ValueError(
                "a deadline clock (ComputeClock(deadline_s=)) can cut rounds "
                "with ZERO arrivals — pass quorum >= 1 so they degrade to "
                "recorded no-ops instead of a 0-client mean"
            )
        if watchdog:
            if watchdog_patience < 1:
                raise ValueError(
                    f"watchdog_patience must be >= 1, got {watchdog_patience}")
            if watchdog_factor <= 1.0:
                raise ValueError(
                    "watchdog_factor must be > 1 (a divergence threshold "
                    f"RELATIVE to the best f̄ seen), got {watchdog_factor}")
            if store == "offload":
                raise ValueError(
                    "the watchdog keeps a full state snapshot in the carry "
                    "— under store='offload' that would double the "
                    "host-resident buffers; run the watchdog with "
                    "store='dense'/'active'"
                )
        if checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every must be >= 0, got {checkpoint_every}")
        ckpt_on = checkpoint_every > 0 or resume
        if ckpt_on:
            if checkpoint_dir is None:
                raise ValueError(
                    "checkpoint_every/resume need a checkpoint_dir= to write "
                    "to / restore from")
            if mesh is not None:
                raise ValueError(
                    "checkpointing round-trips the carry through host npz — "
                    "not supported under a mesh (GSPMD carry placements); "
                    "checkpoint unsharded runs"
                )
            if auto_chunk:
                raise ValueError(
                    "chunk_size='auto' picks chunk boundaries from "
                    "wall-clock timings — pass a fixed chunk_size when "
                    "checkpointing so the save points are deterministic"
                )
            if not scan and store != "offload":
                raise ValueError(
                    "checkpointing rides the chunked scan driver (or the "
                    "host-driven offload loop) — drop scan=False"
                )
        byte_clock = (clock is not None
                      and getattr(clock, "bandwidth_bps", None) is not None)
        if byte_clock:
            # logical model size BEFORE the lane-padding ravel: the wire
            # never carries padding (core/compress.py)
            model_size = pt.tree_size(state["x"])
            clock = clock.with_wire(
                compress.uplink_bytes(wire_comp, model_size),
                compress.downlink_bytes(model_size),
            )
        if overlap == "scatter" and clock is not None:
            # overlapped rounds pay max(compute, comm) instead of their sum
            clock = clock.with_overlap()
        fp = None
        if ckpt_on:
            fp = _config_fingerprint(
                algo=getattr(algo, "name", type(algo).__name__),
                num_clients=algo.fed.num_clients,
                tol=tol, tol_metric=tol_metric, flat=bool(flat), store=store,
                aggregate=aggregate, overlap=overlap,
                async_rounds=bool(async_rounds), max_staleness=max_staleness,
                stale_weighting=stale_weighting, stale_decay=stale_decay,
                participation=participation, clock=clock,
                compression=wire_comp,
                error_feedback=bool(error_feedback), topk_frac=topk_frac,
                faults=faults, screening=screening, quorum=quorum,
                watchdog=bool(watchdog), watchdog_patience=watchdog_patience,
                watchdog_factor=watchdog_factor)
        caller_state = state
        spec = pt.ravel_spec(state["x"]) if flat else None
        if flat:
            # the ONE ravel of the run: everything downstream carries the
            # contiguous buffers; the inverse runs at the return boundary.
            state = flatten_state(algo, state, spec)
            if compressor is not None and compressor.error_feedback \
                    and "ef" not in state:
                state["ef"] = jnp.zeros(
                    (algo.fed.num_clients, spec.padded_size), spec.dtype)
            if faults is not None and faults.needs_prev \
                    and "fault_prev" not in state:
                # the replay fault's stale-upload buffer: engine-created like
                # "ef" above, rides `flat_client_keys` so it shards, offloads
                # and unflattens like any other per-client flat buffer
                state["fault_prev"] = jnp.zeros(
                    (algo.fed.num_clients, spec.padded_size), spec.dtype)
            if overlap == "scatter":
                # seed the double-buffered carry slot: row 0 = the initial
                # anchor (== mean(z⁰) for FedGiA, == the barrier's round-0
                # anchor for the baselines), extra rows (algorithm riders,
                # e.g. SCAFFOLD's control-variate delta) = exact zeros.
                rows = int(getattr(algo, "overlap_slot_rows", 1))
                slot0 = state["x"][None]
                if rows > 1:
                    slot0 = jnp.concatenate([
                        slot0,
                        jnp.zeros((rows - 1, spec.padded_size), slot0.dtype),
                    ])
                state["ovl_shard"] = slot0
        if store != "offload":
            round_fn = make_round_fn(algo, mesh, client_axis, masked=masked,
                                     stale=async_rounds, flat_spec=spec,
                                     active_capacity=active_capacity,
                                     compressor=compressor, overlap=overlap,
                                     donate_kernel=donate_kernel,
                                     aggregate=aggregate,
                                     faults=faults, screening=screening)
        if mesh is not None:
            state, batch = shard_inputs(algo, state, batch, mesh, client_axis)
        if consume:
            _release(caller_state, keep=state)
        del caller_state
        if donate is None:
            # CPU XLA cannot alias buffers; donating would only emit warnings
            donate = jax.default_backend() != "cpu"
        stale0 = (
            api.init_stale_xbar(state["x"], algo.fed.num_clients,
                                max_staleness, weighting=stale_weighting,
                                decay=stale_decay)
            if async_rounds else ()
        )
        guard = _make_guard(quorum, watchdog, watchdog_patience,
                            watchdog_factor)
        ws0 = ()
        if watchdog:
            # the snapshot slot starts as a COPY of the initial state: a
            # shared buffer would alias the donated carry's state leaves
            ws0 = {"best": jnp.full((), jnp.inf, jnp.float32),
                   "bad": jnp.zeros((), jnp.int32),
                   "snap": jax.tree.map(jnp.copy, state)}
        # the host-driven loops below are not preparation
        if store == "offload":
            prepare.close()
            res = _run_offload_loop(
                algo, state, batch, num_rounds, tol, tol_metric,
                participation, clock, stale0, async_rounds, spec,
                active_capacity, compressor, donate_kernel,
                packed=(aggregate == "packed"), max_staleness=max_staleness,
                faults=faults, screening=screening,
                quorum=quorum, checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir, resume=resume, fingerprint=fp)
            return dataclasses.replace(
                res, state=unflatten_state(algo, res.state, spec))
        if not scan:
            prepare.close()
            res = _run_legacy_loop(round_fn, state, batch, num_rounds, tol,
                                   tol_metric, participation, stale0,
                                   async_rounds, clock, guard=guard, ws0=ws0,
                                   donate=donate and mesh is None)
            if flat:
                st = res.state
                if overlap == "scatter":
                    st = _finalize_overlap(algo, st)
                res = dataclasses.replace(
                    res, state=unflatten_state(algo, st, spec))
            return res
        if auto_chunk:
            chunk_size = AUTO_CHUNK_CANDIDATES[0]
        elif chunk_size <= 0:
            chunk_size = num_rounds if tol <= 0 else min(num_rounds, 32)

        pstate = participation.init() if participation is not None else ()
        cstate = clock.init() if clock is not None else ()

        def call_round(st, b, ps, cs, sl, n):
            """One round + advanced policy/clock/staleness state (from the
            carry)."""
            if clock is not None:
                mask, now, cs2 = clock.tick(cs, n)
                s2, sl2, met = round_fn(st, b, mask, sl)
                met = _with_staleness_metrics(met, sl2)
                met["sim_time"] = now
                if byte_clock:
                    met = _with_byte_metrics(met, mask, clock)
                return s2, ps, cs2, sl2, met
            if not masked:
                s2, met = round_fn(st, b)
                return s2, ps, cs, sl, met
            mask, ps2 = participation.mask(ps, n)
            if async_rounds:
                s2, sl2, met = round_fn(st, b, mask, sl)
                return s2, ps2, cs, sl2, _with_staleness_metrics(met, sl2)
            s2, met = round_fn(st, b, mask)
            return s2, ps2, cs, sl, met

        def guarded_round(st, b, ps, cs, sl, ws, n):
            """One round + the quorum/watchdog guard (identity — and
            structurally absent — when both are off)."""
            s2, ps2, cs2, sl2, met = call_round(st, b, ps, cs, sl, n)
            if guard is not None:
                s2, sl2, ws, met = guard(st, sl, s2, sl2, ws, met)
            return s2, ps2, cs2, sl2, ws, met

        _, _, _, _, _, abs_met = jax.eval_shape(
            guarded_round, state, batch, pstate, cstate, stale0, ws0,
            jnp.zeros((), jnp.int32)
        )

        def chunk_fn(carry, batch, *, length):
            def step(carry, _):
                st, ps, cs, sl, ws, done, n = carry
                if tol > 0:
                    def live(op):
                        st_, ps_, cs_, sl_, ws_, b_, n_ = op
                        s2, ps2, cs2, sl2, ws2, met = guarded_round(
                            st_, b_, ps_, cs_, sl_, ws_, n_)
                        return (s2, ps2, cs2, sl2, ws2, met,
                                met[tol_metric] < tol, n_ + 1)

                    def frozen(op):
                        st_, ps_, cs_, sl_, ws_, _, n_ = op
                        zeros = jax.tree.map(
                            lambda l: jnp.zeros(l.shape, l.dtype), abs_met
                        )
                        return (st_, ps_, cs_, sl_, ws_, zeros,
                                jnp.ones((), bool), n_)

                    s2, ps2, cs2, sl2, ws2, met, d2, n2 = jax.lax.cond(
                        done, frozen, live, (st, ps, cs, sl, ws, batch, n)
                    )
                else:
                    s2, ps2, cs2, sl2, ws2, met = guarded_round(
                        st, batch, ps, cs, sl, ws, n)
                    d2, n2 = done, n + 1
                return (s2, ps2, cs2, sl2, ws2, d2, n2), met

            return jax.lax.scan(step, carry, None, length=length)

        donate_args = (0,) if donate else ()
        if donate and not consume:
            # donation must never consume the CALLER's buffers (states are
            # routinely reused across run_rounds calls, e.g. scan-vs-loop
            # comparisons); copy once up front so every donated carry after
            # that is engine-owned.
            state = jax.tree.map(jnp.copy, state)
        chunks: Dict[int, Any] = {}

        def get_chunk(length: int):
            if length not in chunks:
                chunks[length] = jax.jit(
                    functools.partial(chunk_fn, length=length),
                    donate_argnums=donate_args,
                )
            return chunks[length]

        carry = (state, pstate, cstate, stale0, ws0, jnp.zeros((), bool),
                 jnp.zeros((), jnp.int32))

        start_round = 0
        saved_hist = None
        if resume:
            step0 = ckpt_io.latest_step(checkpoint_dir)
            if step0 is not None:
                # fingerprint FIRST (json only): a mismatched config often
                # also means a mismatched carry structure, and the clean
                # error must win over an npz leaf-count assertion
                _check_fingerprint(checkpoint_dir, step0, fp)
                # history dtypes come from abs_met (shapes from the file);
                # the fingerprint guarantees the key set matches
                hist_like = {k: np.zeros((0,), l.dtype)
                             for k, l in abs_met.items()}
                (carry, saved_hist), _ = ckpt_io.load_checkpoint(
                    checkpoint_dir, step0, (carry, hist_like))
                start_round = step0

        # chunk_size="auto": the first chunks run the candidate lengths in
        # turn (clipped to the rounds left — the rounds executed are the same
        # whatever the timings), then the fastest per-round candidate drives
        # the remainder.
        plan = None
        if auto_chunk:
            plan, rem_after = [], num_rounds
            for cand in AUTO_CHUNK_CANDIDATES:
                if rem_after <= 0:
                    break
                plan.append(min(cand, rem_after))
                rem_after -= plan[-1]

        lengths = ()  # compiled lazily by get_chunk
        if mesh is None and not ckpt_on:
            # Pre-compile (AOT) every chunk length this run can need — at
            # most two (fixed chunk) or the candidate set plus each possible
            # remainder (auto) — so wall_s measures execution, matching the
            # legacy warm-up convention. The compiled executables are called
            # directly; on a single device input/output placements are
            # trivially consistent. (Under a mesh, GSPMD may re-place carry
            # leaves between chunks, so there we let jit handle compilation on
            # first call instead. With checkpointing on, chunk lengths are
            # additionally capped at checkpoint boundaries — those compile
            # lazily via get_chunk, so wall_s may include compile time.)
            if auto_chunk:
                lengths = set(plan)
                if tol <= 0 and rem_after > 0:
                    # whatever candidate wins, the remainder runs full chunks
                    # of it plus one partial chunk
                    for cand in set(plan):
                        lengths.add(min(cand, rem_after))
                        if rem_after % cand:
                            lengths.add(rem_after % cand)
            else:
                lengths = {min(chunk_size, num_rounds)}
                if num_rounds % chunk_size and tol <= 0:
                    # with tol off the remainder chunk always runs; with tol
                    # on, converging runs never reach it, so leave it to
                    # compile lazily (get_chunk falls back to plain jit on
                    # first call)
                    lengths.add(num_rounds % chunk_size)

    abs_of = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    for length in lengths:
        with jax.profiler.TraceAnnotation("run_rounds.lower"):
            lowered = get_chunk(length).lower(
                jax.tree.map(abs_of, carry), jax.tree.map(abs_of, batch))
        with jax.profiler.TraceAnnotation("run_rounds.compile"):
            chunks[length] = lowered.compile()

    chunk_metrics = [] if saved_hist is None else [saved_hist]
    timings = []
    remaining = num_rounds - start_round
    executed = start_round
    next_ckpt = None
    if checkpoint_every > 0:
        next_ckpt = (executed // checkpoint_every + 1) * checkpoint_every
    t0 = time.perf_counter()
    while remaining > 0:
        if plan:
            c = plan.pop(0)
            tc = time.perf_counter()
            carry, mets = get_chunk(c)(carry, batch)
            jax.block_until_ready(carry[6])
            timings.append(((time.perf_counter() - tc) / c, c))
            if not plan:
                chunk_size = min(timings)[1]
        else:
            c = min(chunk_size, remaining)
            if next_ckpt is not None:
                # cut the chunk at the checkpoint boundary so the saved
                # carry sits exactly at a multiple of checkpoint_every —
                # the rounds executed are identical whatever the cuts
                c = min(c, next_ckpt - executed)
            carry, mets = get_chunk(c)(carry, batch)
        chunk_metrics.append(mets)
        remaining -= c
        executed += c
        if next_ckpt is not None and executed == next_ckpt:
            _save_scan_checkpoint(checkpoint_dir, executed, carry,
                                  chunk_metrics, fp)
            next_ckpt += checkpoint_every
        if tol > 0 and bool(carry[5]):  # the chunk's ONE host sync
            break
    state, _, _, _, _, done, n = carry
    jax.block_until_ready(n)
    wall = time.perf_counter() - t0

    with jax.profiler.TraceAnnotation("run_rounds.fetch"):
        rounds_run = int(n)
        stopped = tol > 0 and bool(jax.device_get(done))
        mets_host = jax.device_get(chunk_metrics)
        history = {
            k: np.concatenate(
                [np.asarray(m[k]) for m in mets_host])[:rounds_run]
            for k in mets_host[0]
        }
        if flat:
            if overlap == "scatter":
                state = _finalize_overlap(algo, state)
            state = unflatten_state(algo, state, spec)
    return RoundResult(state, history, rounds_run, stopped, wall)


def _finalize_overlap(algo, state):
    """Fold the overlap carry slot back into the state at the return
    boundary: the slot's row 0 holds the LAST round's consensus mean —
    exactly the ``x`` the barrier engine would have stored — and extra
    rows hold algorithm riders. ``algo.overlap_finalize(state, slot)``
    overrides (FedGiA keeps its x — its round stores the consensus it
    used, never lagging; SCAFFOLD also folds the deferred control-variate
    delta); the default recovers ``x = slot[0]``. Runs OUTSIDE the round
    (plain ops on the global, possibly column-sharded slot)."""
    state = dict(state)
    slot = state.pop("ovl_shard")
    fin = getattr(algo, "overlap_finalize", None)
    if fin is not None:
        return fin(state, slot)
    state["x"] = slot[0]
    return state


def _make_guard(quorum: int, watchdog: bool, patience: int, factor: float):
    """Build the post-round QUORUM + WATCHDOG guard, or None when both are
    off (the guarded round is then structurally the unguarded one).

    The guard is pure and traceable — it runs INSIDE the jitted round
    step, so scan == legacy holds for degraded/rollback rounds exactly as
    for ordinary ones:

      * quorum: a round whose accepted-upload count (`screened` when the
        hardening stage ran, else `selected`) falls below `quorum` is a
        recorded no-op — every state entry except the rng and the round
        counter reverts (those two always advance: replaying a round
        index would re-draw the SAME faults/masks forever), the StaleXbar
        reverts with it (the download belongs to the aborted round), and
        the round's history row records `degraded=True`.
      * watchdog: tracks the best f̄ and a full state snapshot; after
        `patience` consecutive committed rounds with f̄ > factor × best
        (NaN counts as diverged), the state rolls back to the snapshot
        (rng/round again excepted) and the row records `rollback=True`.
        Degraded rounds freeze the patience counter — a quorum no-op is
        not evidence of divergence.
    """
    if not quorum and not watchdog:
        return None
    keep = ("rng", "round")

    def merge(flag, a, b, keep=keep):
        """flag ? a : b over two same-structure state dicts; `keep` keys
        always come from `a` (the freshly advanced state)."""
        return {
            k: (a[k] if k in keep else jax.tree.map(
                lambda x, y: jnp.where(flag, x, y), a[k], b[k]))
            for k in a
        }

    def guard(st_old, sl_old, s2, sl2, ws, met):
        met = dict(met)
        ok = jnp.ones((), bool)
        if quorum:
            n_eff = met.get("screened", met["selected"])
            ok = n_eff >= quorum
            s2 = merge(ok, s2, st_old)
            if sl_old != ():
                sl2 = jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b), sl2, sl_old)
            met["degraded"] = jnp.logical_not(ok)
        if watchdog:
            f = met["f_xbar"]
            best, bad, snap = ws["best"], ws["bad"], ws["snap"]
            improved = jnp.logical_and(ok, f < best)
            best2 = jnp.where(improved, f, best)
            snap2 = merge(improved, s2, snap, keep=())
            # NaN f̄ fails the <= and counts as diverged
            diverged = jnp.logical_and(
                ok, jnp.logical_not(f <= jnp.float32(factor) * best2))
            bad2 = jnp.where(ok, jnp.where(diverged, bad + 1, 0), bad)
            roll = bad2 >= patience
            s2 = merge(jnp.logical_not(roll), s2, snap2)
            ws = {"best": best2, "bad": jnp.where(roll, 0, bad2),
                  "snap": snap2}
            met["rollback"] = roll
        return s2, sl2, ws, met

    return guard


def _config_fingerprint(**knobs) -> str:
    """Round-semantics fingerprint embedded in every checkpoint: resume
    refuses a checkpoint written under a different configuration (the
    carry would often deserialize fine, but the continued rounds would
    not be the run the caller asked for). `num_rounds` is deliberately
    NOT part of it — extending a finished run is the point of resuming.
    Deliberately coarse: dataclass knobs (fault model, screening) hash
    by repr, stateful objects (clock, policy, codec) by type + name."""
    def desc(v):
        if v is None or isinstance(v, (bool, int, float, str)):
            return v
        if dataclasses.is_dataclass(v):
            return repr(v)
        return [type(v).__name__, getattr(v, "name", None),
                getattr(v, "deadline_s", None)]

    payload = {k: desc(v) for k, v in knobs.items()}
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()


def _check_fingerprint(checkpoint_dir, step0, fp):
    """Vet the checkpoint's config fingerprint from its json metadata
    alone, BEFORE the carry is deserialized — a config change often also
    changes the carry/history structure, and the leaf-count assertion
    inside load_checkpoint would otherwise mask the real problem."""
    extra = ckpt_io.load_extra(checkpoint_dir, step0)
    if extra.get("fingerprint") != fp:
        raise ValueError(
            f"resume: checkpoint ckpt_{step0:08d} under "
            f"{checkpoint_dir!r} was written by a run with a "
            "different configuration (fingerprint mismatch) — "
            "resuming it would not continue the run it started")


def _save_scan_checkpoint(directory, step, carry, chunk_metrics, fp):
    """Write the scan driver's FULL carry (state incl. ef/fault_prev/
    overlap slot, policy/clock state, StaleXbar, watchdog slot, stop
    flag, round counter) plus the history accumulated so far — one
    atomic npz through checkpoint/checkpoint.py. The history is trimmed
    to the rounds actually run (a tol-stopped chunk emits frozen zero
    rows past the stop), so a resumed run reassembles the exact history
    the uninterrupted run would return."""
    carry_h = jax.device_get(carry)
    n_now = int(carry_h[6])
    mets_host = jax.device_get(chunk_metrics)
    hist = {
        k: np.concatenate([np.asarray(m[k]) for m in mets_host])[:n_now]
        for k in mets_host[0]
    }
    ckpt_io.save_checkpoint(directory, step, (carry_h, hist),
                            extra={"fingerprint": fp})


def _with_byte_metrics(met, mask, clock):
    """Per-round wire totals under a byte-accurate clock: every ARRIVED
    client paid one upload (the codec's wire) and one fp32 download this
    round. Only emitted when `bandwidth_bps` is set — the metric key set
    of plain clocked runs is unchanged."""
    met = dict(met)
    n_arr = jnp.sum(mask.astype(jnp.float32))
    met["bytes_up"] = n_arr * jnp.float32(clock.bytes_up)
    met["bytes_down"] = n_arr * jnp.float32(clock.bytes_down)
    return met


def _with_staleness_metrics(met, stale):
    """Append the async staleness diagnostics to a round's metric dict:
    `staleness` — the (m,) per-client staleness of the anchor each client
    used this round (stacks to a (rounds, m) history) — and its max."""
    met = dict(met)
    met["staleness"] = stale.last_used
    met["staleness_max"] = jnp.max(stale.last_used)
    return met


def _run_legacy_loop(round_fn, state, batch, num_rounds, tol, tol_metric,
                     participation=None, stale0=(), async_rounds=False,
                     clock=None, guard=None, ws0=(), donate=False):
    """Per-round jit dispatch + per-round host sync (the --no-scan path).

    With a participation policy the per-round jitted step also advances the
    policy state and draws the round's mask — the same pure `policy.mask`
    sequence as the scan path, so masks (and results) agree between paths.
    The async `StaleXbar` state, the wall-clock simulation state and the
    quorum/watchdog guard (`_make_guard`, with its watchdog slot `ws0`)
    thread through the step the same way, so async/clock/fault-tolerant
    scan == legacy holds exactly as well.
    """
    if clock is not None:
        byte_clock = getattr(clock, "bandwidth_bps", None) is not None

        def base_step(st, ps, cs, sl, b, n):
            mask, now, cs2 = clock.tick(cs, n)
            s2, sl2, met = round_fn(st, b, mask, sl)
            met = _with_staleness_metrics(met, sl2)
            met["sim_time"] = now
            if byte_clock:
                met = _with_byte_metrics(met, mask, clock)
            return s2, ps, cs2, sl2, met
        pstate, cstate = (), clock.init()
    elif participation is None:
        def base_step(st, ps, cs, sl, b, n):
            s2, met = round_fn(st, b)
            return s2, ps, cs, sl, met
        pstate, cstate = (), ()
    elif async_rounds:
        def base_step(st, ps, cs, sl, b, n):
            mask, ps2 = participation.mask(ps, n)
            s2, sl2, met = round_fn(st, b, mask, sl)
            return s2, ps2, cs, sl2, _with_staleness_metrics(met, sl2)
        pstate, cstate = participation.init(), ()
    else:
        def base_step(st, ps, cs, sl, b, n):
            mask, ps2 = participation.mask(ps, n)
            s2, met = round_fn(st, b, mask)
            return s2, ps2, cs, sl, met
        pstate, cstate = participation.init(), ()

    def step(st, ps, cs, sl, ws, b, n):
        s2, ps2, cs2, sl2, met = base_step(st, ps, cs, sl, b, n)
        if guard is not None:
            s2, sl2, ws, met = guard(st, sl, s2, sl2, ws, met)
        return s2, ps2, cs2, sl2, ws, met

    sstate = stale0
    wstate = ws0
    if donate:
        # Donate the model-size round state — plus the async anchor and
        # the watchdog slot, which also carry model-size buffers — into
        # each per-round dispatch, so the baselines' flat GD rounds (and
        # every other legacy round) update in-place like the scan path's
        # donated carry: no second (m, N) client buffer materialises per
        # round. AOT lower().compile() replaces the executing warm-up
        # (an executed call would consume the donated inputs); the
        # one-time copies keep the caller's arrays valid for round 0.
        state = jax.tree.map(jnp.copy, state)
        sstate = jax.tree.map(jnp.copy, sstate)
        wstate = jax.tree.map(jnp.copy, wstate)
        rfn = jax.jit(step, donate_argnums=(0, 3, 4)).lower(
            state, pstate, cstate, sstate, wstate, batch,
            jnp.zeros((), jnp.int32)).compile()
    else:
        rfn = jax.jit(step)
        # warm-up compile outside the timed region (same convention as the
        # scan path's AOT pre-compile); round is pure, result discarded
        _s, _ps, _cs, _sl, _ws, _m = rfn(state, pstate, cstate, sstate,
                                         wstate, batch,
                                         jnp.zeros((), jnp.int32))
        jax.block_until_ready(_m)
    hist = []
    stopped = False
    t0 = time.perf_counter()
    for i in range(num_rounds):
        state, pstate, cstate, sstate, wstate, met = rfn(
            state, pstate, cstate, sstate, wstate, batch, jnp.int32(i))
        met_h = jax.device_get(met)
        hist.append(met_h)
        if tol > 0 and float(met_h[tol_metric]) < tol:
            stopped = True
            break
    wall = time.perf_counter() - t0
    history = {k: np.asarray([h[k] for h in hist]) for k in hist[0]} if hist else {}
    return RoundResult(state, history, len(hist), stopped, wall)


def _run_offload_loop(algo, state, batch, num_rounds, tol, tol_metric,
                      participation, clock, stale0, async_rounds,
                      spec, cap, compressor, donate_kernel, packed,
                      max_staleness, faults=None, screening=None,
                      quorum=0, checkpoint_every=0, checkpoint_dir=None,
                      resume=False, fingerprint=None):
    """Host-driven round loop for ``run_rounds(store="offload")``.

    The resident ``flat_client_keys`` buffers, the per-client batch and
    the StaleXbar anchor live HOST-side (`pt.OffloadStore` /
    `pt.host_put`); the device keeps only the globals (x, rng, scalars,
    FedGiA's gram factors) and the compact (m,) per-client riders
    (participation/clock state, staleness ages). Each round:

      1. the jitted SELECT step draws the mask / packed row ids on
         device (the same pure `policy.mask` / `clock.tick` sequence as
         the scan and legacy drivers, so masks agree between paths);
      2. the host gathers the (capacity, N) participant tiles
         (`pt.gather_rows` — the active store's exact clip semantics)
         and moves them to the device;
      3. the jitted TILE ROUND runs `algo.round_flat_active` with a
         tile-mode `ActiveSet` (`tile_state=True`: state accessors are
         the identity on the pre-gathered tiles, while idx/mask keep
         resident row semantics for the aggregation and the dense (m,)
         riders);
      4. the host scatters the updated tiles back (`pt.scatter_rows`,
         drop semantics) and applies the stale-anchor refresh write
         (`anchor[refresh] = x̄` — the identical row select the
         on-device stores run inside the jit).

    Steps 2/3 are DOUBLE-BUFFERED: the next round's mask draw and
    (read-only) batch-tile gather are dispatched while the current
    round's device compute is in flight; only the MUTABLE state tiles
    wait for the current round's scatter. Off-CPU the device-side tiles
    are donated into the round (fresh buffers every round).

    Gather/scatter is pure data movement, so the loop is BITWISE
    ``store="active"`` (tests/test_store.py). FedGiA's population tile
    (`active_tile="population"`) shuttles the full client buffers +
    batch each round instead — every client is rewritten every round,
    so the win is residency (host RAM bounds m), not per-round traffic;
    its gram factors stay device-resident in the globals.

    Both steps are AOT-compiled before the timed region (the legacy
    warm-up convention); the compiled tile round's `memory_analysis`
    (where the backend exposes it) is reported as
    ``RoundResult.extras["device_peak_bytes"]`` next to
    ``host_resident_bytes``. ``extras`` also names where the store went:
    ``host_memory_kind`` of the resident buffers after the last round
    (`pt.memory_kind`,
    e.g. ``tpu:pinned_host`` or ``cpu:device``), ``tile_memory_kind`` of
    a host-gathered tile (participant tiles only) and
    ``host_placement_reason``, why the store is not in pinned host
    memory (None when it is).
    """
    population = getattr(algo, "active_tile", "participants") == "population"
    client_keys = tuple(k for k in getattr(algo, "flat_client_keys", ())
                        if k in state)
    byte_clock = (clock is not None
                  and getattr(clock, "bandwidth_bps", None) is not None)
    dev = jax.devices()[0]
    to_dev = lambda tree: jax.tree.map(lambda l: jax.device_put(l, dev), tree)

    store = pt.OffloadStore({k: state[k] for k in client_keys})
    gstate = {k: v for k, v in state.items() if k not in client_keys}
    anchor_h = pt.host_put(stale0.anchor) if async_rounds else None
    if population:
        # every client is rewritten every round: the full batch is read
        # on device each round anyway, so it stays device-resident
        batch_h, batch_dev = None, to_dev(batch)
    else:
        batch_h, batch_dev = pt.host_put_tree(batch), None
    host_bytes = store.nbytes
    if batch_h is not None:
        host_bytes += sum(int(l.nbytes) for l in jax.tree.leaves(batch_h))
    if anchor_h is not None:
        host_bytes += int(anchor_h.nbytes)

    if clock is not None:
        def select(pcs, n):
            mask, now, cs2 = clock.tick(pcs, n)
            return mask, pt.make_active_set(mask, cap).idx, now, cs2
        pcs0 = clock.init()
    else:
        def select(pcs, n):
            mask, ps2 = participation.mask(pcs, n)
            return (mask, pt.make_active_set(mask, cap).idx,
                    jnp.float32(0.0), ps2)
        pcs0 = participation.init()

    def tile_round(gst, tiles, batch_t, mask, sl_in):
        st = dict(gst)
        st.update(tiles)
        aset = pt.make_active_set(mask, cap, tile_state=not population,
                                  packed=packed)
        if async_rounds:
            anchor_t, age, last_used = sl_in
            sl = api.StaleXbar(anchor_t, age, last_used, max_staleness,
                               stale0.weighting, stale0.decay)
            s2, sl2, met = algo.round_flat_active(
                st, batch_t, spec, aset, sl, compressor=compressor,
                donate_kernel=donate_kernel, faults=faults,
                screening=screening)
            met = _with_staleness_metrics(met, sl2)
            refresh = None
            if not population and max_staleness > 0:
                # the rows the host-side anchor write must refresh —
                # the view's exact expression on the exact same inputs
                refresh = jnp.logical_or(mask, age > max_staleness)
            sl_out = (sl2.anchor, sl2.age, sl2.last_used, refresh)
        else:
            s2, met = algo.round_flat_active(
                st, batch_t, spec, aset, compressor=compressor,
                donate_kernel=donate_kernel, faults=faults,
                screening=screening)
            sl_out = ()
        s2 = dict(s2)
        tiles2 = {k: s2.pop(k) for k in client_keys}
        return s2, tiles2, met, sl_out

    abs_of = lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype)
    tile_abs = lambda tree: jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((cap,) + l.shape[1:], l.dtype), tree)
    n0_abs = jax.ShapeDtypeStruct((), jnp.int32)
    pcs_abs = jax.tree.map(abs_of, pcs0)
    mask_abs, _, _, _ = jax.eval_shape(select, pcs_abs, n0_abs)
    select_c = jax.jit(select).lower(pcs_abs, n0_abs).compile()
    if population:
        tiles_abs = {k: abs_of(v) for k, v in store.buffers.items()}
        batch_abs = jax.tree.map(abs_of, batch_dev)
        anchor_abs = abs_of(anchor_h) if async_rounds else None
    else:
        tiles_abs = tile_abs(store.buffers)
        batch_abs = tile_abs(batch_h)
        anchor_abs = (jax.ShapeDtypeStruct((cap,) + anchor_h.shape[1:],
                                           anchor_h.dtype)
                      if async_rounds else None)
    sl_abs = ((anchor_abs, abs_of(stale0.age), abs_of(stale0.last_used))
              if async_rounds else ())
    if jax.default_backend() != "cpu":
        # fresh device buffers every round: tiles + (participants) batch
        # tile + staleness inputs are all donatable; the population batch
        # is reused every round and must stay alive
        dn = (1, 4) if population else (1, 2, 4)
    else:
        dn = ()
    round_c = jax.jit(tile_round, donate_argnums=dn).lower(
        jax.tree.map(abs_of, gstate), tiles_abs, batch_abs, mask_abs,
        sl_abs).compile()

    extras = {"host_resident_bytes": int(host_bytes),
              "device_peak_bytes": None,
              "host_placement_reason": pt.host_placement_reason()}
    ma_fn = getattr(round_c, "memory_analysis", None)
    if ma_fn is not None:
        try:
            ma = ma_fn()
            extras["device_peak_bytes"] = int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        except Exception:
            pass

    gather_h = lambda tree, i: jax.tree.map(
        lambda l: pt.gather_rows(l, i), tree)
    hist = []
    stopped = False
    age = last_used = None
    if async_rounds:
        age, last_used = stale0.age, stale0.last_used

    def ckpt_tree(pcs_at_round_start):
        """The loop's full host-side state: globals, resident buffers,
        stale anchor + ages, and the policy/clock state AS OF the start
        of the next round (its select re-draws bitwise on resume — the
        draw is a pure function of (pcs, round))."""
        return {"gstate": gstate, "store": store.buffers,
                "anchor": anchor_h if async_rounds else (),
                "age": age if async_rounds else (),
                "last_used": last_used if async_rounds else (),
                "pcs": pcs_at_round_start}

    pcs = pcs0
    start_round = 0
    if resume:
        step0 = ckpt_io.latest_step(checkpoint_dir)
        if step0 is not None:
            _check_fingerprint(checkpoint_dir, step0, fingerprint)
            _, _, met_abs, _ = jax.eval_shape(
                tile_round, jax.tree.map(abs_of, gstate), tiles_abs,
                batch_abs, mask_abs, sl_abs)
            hist_like = {k: np.zeros((0,), l.dtype)
                         for k, l in met_abs.items()}
            if clock is not None:
                hist_like["sim_time"] = np.zeros((0,), np.float32)
                if byte_clock:
                    hist_like["bytes_up"] = np.zeros((0,), np.float32)
                    hist_like["bytes_down"] = np.zeros((0,), np.float32)
            if quorum > 0:
                hist_like["degraded"] = np.zeros((0,), bool)
            (snap, saved_hist), _ = ckpt_io.load_checkpoint(
                checkpoint_dir, step0, (ckpt_tree(pcs0), hist_like))
            gstate = snap["gstate"]
            store.buffers = {k: pt.host_put(v)
                             for k, v in snap["store"].items()}
            if async_rounds:
                anchor_h = pt.host_put(snap["anchor"])
                age, last_used = snap["age"], snap["last_used"]
            pcs = snap["pcs"]
            saved_hist = jax.device_get(saved_hist)
            hist = [{k: saved_hist[k][t] for k in saved_hist}
                    for t in range(step0)]
            start_round = step0
    mask, idx, now, pcs = select_c(pcs, jnp.int32(start_round))
    if population:
        idx_h, staged = None, batch_dev
    else:
        idx_h = pt.host_put(idx)
        tile_h = gather_h(batch_h, idx_h)
        extras["tile_memory_kind"] = pt.memory_kind(
            jax.tree.leaves(tile_h)[0])
        staged = to_dev(tile_h)
    t0 = time.perf_counter()
    for i in range(start_round, num_rounds):
        if population:
            tiles = to_dev(store.buffers)
            sl_in = ((to_dev(anchor_h), age, last_used)
                     if async_rounds else ())
        else:
            tiles = to_dev(store.gather_tiles(idx_h))
            sl_in = ((to_dev(pt.gather_rows(anchor_h, idx_h)), age,
                      last_used) if async_rounds else ())
        out = round_c(gstate, tiles, staged, mask, sl_in)
        cur_mask, cur_idx_h, cur_now = mask, idx_h, now
        pcs_prev = pcs
        if i + 1 < num_rounds:
            # double-buffer: next round's mask draw + read-only batch
            # tile overlap the in-flight device round; the mutable state
            # tiles wait for this round's scatter below
            mask, idx, now, pcs = select_c(pcs, jnp.int32(i + 1))
            if not population:
                idx_h = pt.host_put(idx)
                staged = to_dev(gather_h(batch_h, idx_h))
        gstate_new, tiles2, met, sl_out = out
        met = dict(met)
        if clock is not None:
            met["sim_time"] = cur_now
            if byte_clock:
                met = _with_byte_metrics(met, cur_mask, clock)
        degraded = False
        if quorum > 0:
            # the accept/reject decision gates the host-side commit, so
            # the round's count must reach the host BEFORE the scatter —
            # one extra device sync per round, paid only under quorum
            n_eff = met.get("screened", met["selected"])
            degraded = bool(jax.device_get(n_eff) < quorum)
            met["degraded"] = np.asarray(degraded)
        if degraded:
            # recorded no-op (run_rounds' quorum contract): resident
            # tiles, stale anchor and ages keep their pre-round values;
            # only the rng and the round counter advance
            gstate = {k: (gstate_new[k] if k in ("rng", "round")
                          else gstate[k]) for k in gstate_new}
        else:
            gstate = gstate_new
            if population:
                store.buffers = {k: pt.host_put(v)
                                 for k, v in tiles2.items()}
            else:
                store.scatter_tiles(cur_idx_h, tiles2)
            if async_rounds:
                anchor_new, age, last_used, refresh = sl_out
                if population:
                    anchor_h = pt.host_put(anchor_new)
                elif max_staleness > 0:
                    # the dense refresh write, host-side: participant +
                    # force-synced rows take the fresh x̄ — bitwise the
                    # on-device stores' row select (same inputs, same op)
                    anchor_h = jnp.where(
                        pt.host_put(refresh)[:, None],
                        pt.host_put(anchor_new)[None, :], anchor_h)
        met_h = jax.device_get(met)
        hist.append(met_h)
        if tol > 0 and float(met_h[tol_metric]) < tol:
            stopped = True
            break
        if checkpoint_every > 0 and (i + 1) % checkpoint_every == 0:
            # saved AFTER the stop check: a run that stops at a boundary
            # writes no checkpoint for it, so a resume re-runs and
            # re-stops at the same round — bitwise the uninterrupted run
            hist_np = {k: np.asarray([h[k] for h in hist])
                       for k in hist[0]}
            ckpt_io.save_checkpoint(
                checkpoint_dir, i + 1,
                (jax.device_get(ckpt_tree(pcs_prev)), hist_np),
                extra={"fingerprint": fingerprint})
    wall = time.perf_counter() - t0
    # where the resident buffers ARE after the last round's scatter
    resident = (list(store.buffers.values()) + jax.tree.leaves(batch_h)
                + ([anchor_h] if async_rounds else []))
    extras["host_memory_kind"] = (pt.memory_kind(resident[0])
                                  if resident else None)
    state_f = dict(gstate)
    for k, b in store.buffers.items():
        state_f[k] = jax.device_put(b, dev)
    history = ({k: np.asarray([h[k] for h in hist]) for k in hist[0]}
               if hist else {})
    return RoundResult(state_f, history, len(hist), stopped, wall, extras)


# --------------------------------------------------------------- generic scan
def scan_steps(step_fn, num_steps: int, *, donate_carry: bool = False):
    """Compile `num_steps` applications of `carry -> (carry, out)` into one
    jitted `lax.scan` — one dispatch for the whole loop. Extra positional
    args are passed through to every step (use for params so they are jit
    arguments, not baked-in constants). Used by the serving decode loop."""

    def run(carry, *args):
        def body(c, _):
            return step_fn(c, *args)

        return jax.lax.scan(body, carry, None, length=num_steps)

    return jax.jit(run, donate_argnums=(0,) if donate_carry else ())
