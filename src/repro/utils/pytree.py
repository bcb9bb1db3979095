"""Pytree arithmetic helpers used by all federated algorithms.

Every federated algorithm in this repo manipulates whole model states
(parameters, duals, control variates) as pytrees; these helpers keep that
code readable and fusion-friendly (jnp ops only, no python loops over
leaves at trace time beyond tree_map).

The `RavelSpec` family (`ravel_spec` / `RavelSpec.ravel` /
`RavelSpec.ravel_stacked` / `RavelSpec.unravel`) is the flat-buffer layout
the round engine's hot path runs on: the model pytree is flattened ONCE
per `run_rounds` call into a single lane-padded (N,) vector (client state:
one (m, N) buffer), every round's elementwise math and eq. (11)'s
all-reduce operate on that contiguous buffer, and the pytree is only
reconstructed at the gradient/metric/return boundaries. See
docs/engine.md#flat-buffer-round-state.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Tuple

import jax
import jax.numpy as jnp

Pytree = object

# Debug-mode invariant checking: with REPRO_DEBUG_TAIL=1, every unravel
# asserts the lane-padded tail of the flat buffer is still exactly zero
# (the invariant eq. (11)'s norms and the Pallas kernel rely on). Off by
# default — the check inserts a host callback per unravel.
DEBUG_TAIL = os.environ.get("REPRO_DEBUG_TAIL", "0") not in ("", "0")


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_mul(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.multiply, a, b)


def tree_scale(a: Pytree, s) -> Pytree:
    return jax.tree.map(lambda x: x * s, a)


def tree_axpy(alpha, x: Pytree, y: Pytree) -> Pytree:
    """alpha*x + y."""
    return jax.tree.map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_zeros_like(a: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, a)


def tree_ones_like(a: Pytree) -> Pytree:
    return jax.tree.map(jnp.ones_like, a)


def tree_dot(a: Pytree, b: Pytree) -> jax.Array:
    leaves = jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b)
    return jax.tree.reduce(jnp.add, leaves, jnp.float32(0.0))


def tree_sq_norm(a: Pytree) -> jax.Array:
    return tree_dot(a, a)


def tree_norm(a: Pytree) -> jax.Array:
    return jnp.sqrt(tree_sq_norm(a))


def tree_size(a: Pytree) -> int:
    return sum(x.size for x in jax.tree.leaves(a))


def tree_bytes(a: Pytree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(a))


def tree_cast(a: Pytree, dtype) -> Pytree:
    return jax.tree.map(lambda x: x.astype(dtype), a)


def tree_where(pred, a: Pytree, b: Pytree) -> Pytree:
    """Leaf-wise select; pred is a scalar (or broadcastable) bool array."""
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def tree_mean_over_axis(a: Pytree, axis: int = 0) -> Pytree:
    return jax.tree.map(lambda x: jnp.mean(x, axis=axis), a)


def tree_stack(trees, axis: int = 0) -> Pytree:
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=axis), *trees)


def tree_unstack(tree, axis: int = 0):
    leaves, treedef = jax.tree.flatten(tree)
    n = leaves[0].shape[axis]
    return [
        jax.tree.unflatten(treedef, [jnp.take(l, i, axis=axis) for l in leaves])
        for i in range(n)
    ]


def tree_allclose(a: Pytree, b: Pytree, rtol=1e-5, atol=1e-6) -> bool:
    oks = jax.tree.map(
        lambda x, y: bool(jnp.allclose(x, y, rtol=rtol, atol=atol)), a, b
    )
    return all(jax.tree.leaves(oks))


# --------------------------------------------------------------------------
# Flat-buffer layout: ravel a model pytree once, run the round on the
# contiguous vector, unravel only at gradient/metric/return boundaries.
# --------------------------------------------------------------------------
LANES = 128  # TPU vector-register lane width; the flat buffer is padded to
# a multiple of it so the Pallas round kernel never re-pads on the hot path


@dataclasses.dataclass(frozen=True)
class RavelSpec:
    """Cached flatten layout for a model pytree.

    Records the treedef plus per-leaf shapes/dtypes/offsets into a single
    1-D buffer of ``size`` elements, lane-padded to ``padded_size``
    (``LANES``-multiple, zeros in the tail). The buffer dtype is the
    result-type promotion of the leaf dtypes, so an unravel->ravel round
    trip is exact (leaves are cast to a wider-or-equal dtype and back).

    Built by :func:`ravel_spec` (cached on (treedef, shapes, dtypes), so
    repeated `run_rounds` calls on the same model reuse one spec object
    and jit caches keyed on the spec hit).
    """

    treedef: jax.tree_util.PyTreeDef
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[jnp.dtype, ...]
    offsets: Tuple[int, ...]
    size: int
    padded_size: int
    dtype: jnp.dtype

    def ravel(self, tree: Pytree) -> jax.Array:
        """Pytree -> contiguous (padded_size,) vector (zero-padded tail)."""
        leaves = self.treedef.flatten_up_to(tree)
        flat = jnp.concatenate(
            [l.astype(self.dtype).reshape(-1) for l in leaves]
        )
        pad = self.padded_size - self.size
        return jnp.pad(flat, (0, pad)) if pad else flat

    def ravel_stacked(self, tree: Pytree) -> jax.Array:
        """Client-stacked pytree (leading axis m on every leaf) ->
        one contiguous (m, padded_size) buffer."""
        leaves = self.treedef.flatten_up_to(tree)
        m = leaves[0].shape[0]
        flat = jnp.concatenate(
            [l.astype(self.dtype).reshape(m, -1) for l in leaves], axis=1
        )
        pad = self.padded_size - self.size
        return jnp.pad(flat, ((0, 0), (0, pad))) if pad else flat

    def unravel(self, flat: jax.Array) -> Pytree:
        """(padded_size,) vector -> pytree (inverse of :meth:`ravel`)."""
        if DEBUG_TAIL:
            flat = self.check_zero_tail(flat)
        leaves = [
            jax.lax.slice_in_dim(flat, o, o + _size_of(s), axis=-1)
            .reshape(flat.shape[:-1] + s)
            .astype(d)
            for o, s, d in zip(self.offsets, self.shapes, self.dtypes)
        ]
        return self.treedef.unflatten(leaves)

    def unravel_stacked(self, flat: jax.Array) -> Pytree:
        """(m, padded_size) buffer -> client-stacked pytree."""
        return self.unravel(flat)

    def check_zero_tail(self, flat: jax.Array) -> jax.Array:
        """Debug assertion: the lane-padded tail of `flat` is exactly zero.

        Returns `flat` unchanged (so it can be spliced into traced code);
        the check itself runs as a host callback and raises on violation.
        Only called when REPRO_DEBUG_TAIL=1 — the default path never pays
        for it.
        """
        if self.padded_size == self.size or flat.shape[-1] != self.padded_size:
            return flat
        tail = jax.lax.slice_in_dim(
            flat, self.size, self.padded_size, axis=-1
        )
        jax.debug.callback(
            _raise_on_nonzero_tail, jnp.max(jnp.abs(tail)), self.size,
            self.padded_size,
        )
        return flat


def _size_of(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def _raise_on_nonzero_tail(maxabs, size, padded_size):
    if float(maxabs) != 0.0:
        raise AssertionError(
            f"RavelSpec zero-tail invariant violated: |tail|_max = "
            f"{float(maxabs)!r} in pad region [{int(size)}, "
            f"{int(padded_size)}) — an in-place flat-buffer write leaked "
            f"into the lane padding (this silently skews eq. (11) norms)"
        )


_SPEC_CACHE: dict = {}


def ravel_spec(tree: Pytree) -> RavelSpec:
    """Build (or fetch the cached) :class:`RavelSpec` for `tree`'s layout.

    The cache key is (treedef, shapes, dtypes): any two pytrees with the
    same structure share one spec object, so the engine's jit caches —
    which close over the spec — are reused across `run_rounds` calls."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    key = (treedef, shapes, dtypes)
    spec = _SPEC_CACHE.get(key)
    if spec is None:
        sizes = [_size_of(s) for s in shapes]
        offsets, off = [], 0
        for s in sizes:
            offsets.append(off)
            off += s
        padded = -(-off // LANES) * LANES
        spec = RavelSpec(
            treedef=treedef,
            shapes=shapes,
            dtypes=dtypes,
            offsets=tuple(offsets),
            size=off,
            padded_size=padded,
            dtype=jnp.result_type(*dtypes) if dtypes else jnp.dtype("float32"),
        )
        _SPEC_CACHE[key] = spec
    return spec


# --------------------------------------------------------------------------
# Active-set client store: a round touches only the packed tile of the
# clients the participation mask selected, gathered from / scattered back
# to the resident (m, padded_size) flat buffers at the round's boundaries.
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ActiveSet:
    """The round's packed participant tile, derived from a dense mask.

    ``idx`` holds the (sorted) resident-store row ids of this round's
    participants, padded to the static ``capacity`` with the sentinel
    ``num_clients`` (one past the last row). Padding rows gather a
    clamped duplicate of the last resident row (finite garbage — never
    NaN), are zeroed out of every reduction via ``valid``, and are
    dropped on scatter. Because ``idx`` is ascending and zero rows are
    exact identities of a sum, packed reductions over the tile are
    BITWISE equal to the dense masked reductions over all m rows.

    ``capacity`` is static per run: a fixed-cardinality policy (uniform /
    weighted / cyclic) packs to exactly |C| rows; variable-cardinality
    sources (availability, wall-clock arrivals) pack to m rows — correct,
    but no smaller than dense (see docs/engine.md#active-set-client-store).

    ``tile_state`` (static) marks the HOST-OFFLOADED round
    (``run_rounds(store="offload")``): the per-client state buffers the
    round receives are already the pre-gathered (capacity, N) participant
    tiles — the engine gathered them from the host-resident store before
    entering the jit — so :meth:`gather_state` / :meth:`scatter_state`
    become the identity and the engine owns the host-side write-back.
    ``idx`` / ``valid`` / ``count`` / ``mask`` keep their REAL resident
    semantics in both modes: dense (m,)-shaped riders (staleness ages,
    aggregation weights) and the dense-layout aggregation scatter still
    address the true resident rows. See docs/engine.md#host-offloaded-store.

    ``packed`` (static) opts the round's eq. (11) into the fp-tolerance
    PACKED aggregation (``run_rounds(aggregate="packed")``): the
    aggregation sums the (capacity, N) tile directly instead of scattering
    back to the dense (m, N) layout first — O(capacity·N), ~1 ulp from the
    bitwise dense default. See docs/engine.md#packed-aggregation.
    """

    idx: jax.Array  # (capacity,) int32 rows into the resident store
    valid: jax.Array  # (capacity,) bool — False on padding rows
    count: jax.Array  # () float32 — number of participants (== mask sum)
    mask: jax.Array  # (m_local,) bool — the round's dense mask
    capacity: int = dataclasses.field(metadata=dict(static=True))
    num_clients: int = dataclasses.field(metadata=dict(static=True))
    tile_state: bool = dataclasses.field(default=False,
                                         metadata=dict(static=True))
    packed: bool = dataclasses.field(default=False,
                                     metadata=dict(static=True))

    def gather(self, buf: jax.Array) -> jax.Array:
        """Resident (m, ...) buffer -> packed (capacity, ...) tile."""
        return gather_rows(buf, self.idx)

    def scatter(self, buf: jax.Array, tile: jax.Array) -> jax.Array:
        """Write the packed tile back into its resident rows (padding
        rows carry the sentinel index and are dropped)."""
        return scatter_rows(buf, self.idx, tile)

    def gather_state(self, buf: jax.Array) -> jax.Array:
        """Per-client STATE accessor: resident (m, ...) buffer -> packed
        tile — or the identity under ``tile_state`` (the engine already
        gathered the tile from the host-resident store). Algorithms must
        route their `flat_client_keys` reads through this instead of
        :meth:`gather`, which keeps resident row semantics for dense
        (m,)-shaped riders in both modes."""
        return buf if self.tile_state else self.gather(buf)

    def scatter_state(self, buf: jax.Array, tile: jax.Array) -> jax.Array:
        """Per-client STATE write-back twin of :meth:`gather_state`: under
        ``tile_state`` the updated tile is returned as-is (the engine
        scatters it into the host-resident rows outside the jit), else
        the ordinary resident-row scatter."""
        return tile if self.tile_state else self.scatter(buf, tile)

    def gather_tree(self, tree: Pytree) -> Pytree:
        """Gather every leaf's active rows (e.g. the per-client batch).
        Routed through :meth:`gather_state`: the host-offloaded engine
        pre-gathers the batch tile with the state tiles."""
        return jax.tree.map(self.gather_state, tree)

    def zero_invalid(self, tile: jax.Array) -> jax.Array:
        """Zero the padding rows of a (capacity, ...) tile so reductions
        over the tile match the dense masked reductions bitwise."""
        v = self.valid.reshape(self.valid.shape + (1,) * (tile.ndim - 1))
        return jnp.where(v, tile, jnp.zeros_like(tile))


jax.tree_util.register_dataclass(
    ActiveSet,
    data_fields=["idx", "valid", "count", "mask"],
    meta_fields=["capacity", "num_clients", "tile_state", "packed"],
)


def make_active_set(mask: jax.Array, capacity: int, *,
                    tile_state: bool = False,
                    packed: bool = False) -> ActiveSet:
    """Pack a dense (m,) participation mask into an :class:`ActiveSet`.

    ``capacity`` must upper-bound the mask's population count (the engine
    derives it from the policy's fixed cardinality, or uses m); overflow
    would silently drop participants, so callers own that invariant.
    ``tile_state`` / ``packed`` set the static store/aggregation modes
    (see the :class:`ActiveSet` docstring).
    """
    m = mask.shape[0]
    (idx,) = jnp.nonzero(mask, size=capacity, fill_value=m)
    idx = idx.astype(jnp.int32)
    return ActiveSet(
        idx=idx,
        valid=idx < m,
        count=jnp.sum(mask.astype(jnp.float32)),
        mask=mask,
        capacity=capacity,
        num_clients=m,
        tile_state=tile_state,
        packed=packed,
    )


def gather_rows(buf: jax.Array, idx: jax.Array) -> jax.Array:
    """Row gather with clamped out-of-range indices: padding rows read a
    duplicate of the last resident row (finite, deterministic) instead of
    producing NaN, and are masked/dropped downstream."""
    return jnp.take(buf, idx, axis=0, mode="clip")


def scatter_rows(buf: jax.Array, idx: jax.Array, rows: jax.Array) -> jax.Array:
    """Inverse of :func:`gather_rows`: write packed rows back into the
    resident buffer; sentinel (out-of-range) indices are dropped. Under
    buffer donation XLA updates the resident store in place."""
    return buf.at[idx].set(rows, mode="drop")


# --- host-resident placement for run_rounds(store="offload") -----------
#
# The offloaded store keeps the resident (m, N) client buffers in HOST
# memory and only moves (capacity, N) participant tiles to the device
# each round. Placement preference: pinned host memory of the default
# device (sharding memory_kind="pinned_host", zero-copy DMA on TPU/GPU)
# when the backend both accepts it AND can run the row gather/scatter on
# it; otherwise the CPU backend's device, with the reason recorded. On a
# CPU-only process the two coincide and every transfer below is a no-op.

_HOST_PLACEMENT = None  # (placement, reason it is not pinned host or None)


def host_placement():
    """The device/sharding host-resident offload buffers are committed
    to. Probed once per process; the probe runs the exact ops the
    offload store needs (row take / indexed set), so a backend that
    merely *stores* pinned-host arrays but cannot compute on them, or
    computes into device memory, gets the CPU device instead. That
    choice is never silent: it warns, and :func:`host_placement_reason`
    says why (the offload loop reports it in ``RoundResult.extras``)."""
    global _HOST_PLACEMENT
    if _HOST_PLACEMENT is not None:
        return _HOST_PLACEMENT[0]
    placement, reason = None, "the default backend is the CPU"
    if jax.default_backend() != "cpu":
        try:
            sharding = jax.sharding.SingleDeviceSharding(
                jax.devices()[0], memory_kind="pinned_host")
            # several rows: a one-row take lowers to a dynamic slice,
            # which compiles where the store's row gather does not
            probe = jax.device_put(jnp.zeros((4, 1, 8), jnp.float32),
                                   sharding)
            idx = jax.device_put(jnp.arange(3, dtype=jnp.int32), sharding)
            out = scatter_rows(probe, idx, gather_rows(probe, idx))
            jax.block_until_ready(out)
            if out.sharding.memory_kind != "pinned_host":
                # the row ops would move the store into device memory
                raise RuntimeError("row ops on pinned_host arrays return "
                                   f"{out.sharding.memory_kind} memory")
            placement, reason = sharding, None
        except Exception as exc:  # XlaRuntimeError has no stable subclass
            reason = f"pinned_host probe failed: {type(exc).__name__}: {exc}"
            warnings.warn(
                f"offload store placed on the CPU backend ({reason})",
                RuntimeWarning, stacklevel=2)
    if placement is None:
        placement = jax.local_devices(backend="cpu")[0]
    _HOST_PLACEMENT = (placement, reason)
    return placement


def host_placement_reason():
    """Why the offload store is not in pinned host memory, or None when
    it is."""
    host_placement()
    return _HOST_PLACEMENT[1]


def memory_kind(x: jax.Array) -> str:
    """``<platform>:<memory kind>`` of an array, e.g. ``tpu:pinned_host``
    or ``cpu:device``."""
    dev = next(iter(x.devices()))
    return f"{dev.platform}:{x.sharding.memory_kind}"


def host_put(x) -> jax.Array:
    """Commit an array to the offload store's host placement. A failed
    transfer raises: the run does not carry on with its store moved."""
    return jax.device_put(x, host_placement())


def host_put_tree(tree: Pytree) -> Pytree:
    return jax.tree.map(host_put, tree)


class OffloadStore:
    """Host-resident flat client buffers for ``run_rounds(store="offload")``.

    Holds the per-client ``flat_client_keys`` buffers (z/π/h, λ, cᵢ, EF
    residuals) committed to :func:`host_placement`. Gather/scatter reuse
    the exact :func:`gather_rows` / :func:`scatter_rows` semantics of the
    device-resident active store (clip reads, drop writes) — pure data
    movement, so the round tiles carry bit-identical values and the
    offloaded store is bitwise-equal to ``store="active"``. See
    docs/engine.md#host-offloaded-store.
    """

    def __init__(self, buffers: dict):
        self.buffers = {k: host_put(v) for k, v in buffers.items()}

    def gather_tiles(self, idx: jax.Array) -> dict:
        """(capacity,) host row ids -> {key: (capacity, ...) host tile}."""
        return {k: gather_rows(b, idx) for k, b in self.buffers.items()}

    def scatter_tiles(self, idx: jax.Array, tiles: dict) -> None:
        """Write the round's updated tiles back into the resident rows."""
        for k, rows in tiles.items():
            self.buffers[k] = scatter_rows(self.buffers[k], idx,
                                           host_put(rows))

    @property
    def nbytes(self) -> int:
        return sum(int(b.nbytes) for b in self.buffers.values())
