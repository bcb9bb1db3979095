"""What every plain reference shares: products at a stated precision,
the per-client loss and gradient of a problem (`bench/losses/<problem>.py`),
its Lipschitz bound, the data laid out for it, and the participants'
draw. An algorithm's reference is `bench/references/<algorithm>.py`,
found by the configuration's `algorithm`.

A problem file gives either the linear form, `terms` and `regulariser`
of z = A x (and `lipschitz` from A), or, for a model whose parameters
are a pytree, `loss_grad(cfg, data, x, precision)`: per-client f (m,)
and ∇f (m, n) at the flat x, with r the configuration's `lipschitz`.
It may also give `init(cfg, seed)`, the flat x⁰ both sides start from
(otherwise 0, the paper's start), and `counts(cfg)` (bench/counting.py).

Nothing here imports `repro` or takes anything the program made: float32
`jnp`, every product at `Precision.HIGHEST`.

`precision="high"` is the control: every product in three bf16 passes
(hi·hi + hi·lo + lo·hi, accumulated in float32), as a TPU computes a
float32 product at `Precision.HIGH`. It is spelled out here, so that it
reads the same on the CPU and on the chip.
"""
from __future__ import annotations

import importlib
import os
import re
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

HERE = os.path.dirname(os.path.abspath(__file__))
HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "high")
ROW_BLOCK = 1 << 15
_NAME = re.compile(r"^[A-Za-z0-9_]+$")


def load(kind: str, name: str):
    """`bench/<kind>/<name>.py`, found by name: a generator, a loss or an
    algorithm's reference. A name with no file cannot be built."""
    if not _NAME.match(str(name)) or not os.path.exists(
            os.path.join(HERE, kind, f"{name}.py")):
        raise ValueError(f"bench/{kind}/{name}.py does not exist: the "
                         f"benchmark cannot build {kind} {name!r}")
    return importlib.import_module(f"bench.{kind}.{name}")


def by_rows(fn, *arrays):
    """`fn` over blocks of ROW_BLOCK rows of the host `arrays` (of one
    length), on the host's cores, the blocks' results joined by rows.
    Row for row the numbers `fn` gives over the whole arrays; at 10^6
    rows and more in a fraction of the time, and with float64
    temporaries of a block, not of the whole."""
    starts = range(0, len(arrays[0]), ROW_BLOCK)
    with ThreadPoolExecutor(min(len(starts), os.cpu_count() or 1)) as ex:
        parts = list(ex.map(
            lambda s: fn(*(a[s:s + ROW_BLOCK] for a in arrays)), starts))
    return np.concatenate(parts)


def own_gradient(cfg: dict):
    """The problem's own `loss_grad`, or None for the linear form."""
    return getattr(load("losses", cfg["problem"]), "loss_grad", None)


def _bf16(a):
    """`a` rounded to bfloat16's 8 bits of mantissa, kept in float32.
    `reduce_precision`, unlike a round trip through `astype`, is never
    elided by a compiler that allows excess precision (XLA on the TPU
    does, and the control would then compute with lo = 0)."""
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def product(subscripts: str, a, b, precision: str):
    ein = lambda x, y: jnp.einsum(subscripts, x, y, precision=HIGHEST)
    if precision == "highest":
        return ein(a, b)
    ah, al = _split(a)
    bh, bl = _split(b)
    return ein(ah, bh) + (ein(ah, bl) + ein(al, bh))


def scale_rows(a, s, precision: str):
    """Row i of `a` (m, n) times s_i: a product with nothing summed, so
    elementwise (as a dot with no contraction the TPU is slow at it);
    the control makes it of the same three bf16 passes as `product`."""
    if precision == "highest":
        return a * s[:, None]
    ah, al = _split(a)
    sh, sl = _split(s)
    return ah * sh[:, None] + (ah * sl[:, None] + al * sh[:, None])


def lipschitz(cfg: dict, data: dict) -> float:
    """r = max_i of client i's Hessian bound, in float64 on the host; for
    a problem with its own `loss_grad`, the configuration's `lipschitz`."""
    if own_gradient(cfg):
        return float(cfg["lipschitz"])
    def masked(a, k):
        k = np.asarray(k, np.float64)
        return np.asarray(a, np.float64) * k[:, :, None]

    A, mask = np.asarray(data["A"]), np.asarray(data["mask"])
    d = np.maximum(np.asarray(mask, np.float64).sum(axis=1), 1.0)
    if A.shape[1] == 1:
        top = by_rows(lambda a, k: np.sum(masked(a, k)[:, 0, :] ** 2, axis=1),
                      A, mask)
    else:  # the largest eigenvalue of A_i A_iᵀ is that of A_iᵀ A_i
        Am = masked(A, mask)
        top = np.linalg.eigvalsh(np.einsum("mdn,men->mde", Am, Am))[:, -1]
    return load("losses", cfg["problem"]).lipschitz(cfg, top, d)


def device_data(cfg: dict, data: dict, mesh=None) -> dict:
    """The data as the reference reads it, on the device: for the linear
    form (A, b, mask) as `host_arrays` gives them, else the arrays as
    made. Where the cell lies over a mesh, laid by rows over its first
    axis, as the program's clients are, so that the whole population
    fits."""
    arrays = dict(data) if own_gradient(cfg) else \
        dict(zip(("A", "b", "mask"), host_arrays(data)))
    if mesh is None:
        return {k: jnp.asarray(v) for k, v in arrays.items()}
    rows = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    return {k: jax.device_put(np.asarray(v), rows) for k, v in arrays.items()}


def loss_grad(cfg: dict, data: dict, x, precision: str):
    """Per-client f_i(x) (m,) and ∇f_i(x) (m, n) over `device_data`; for
    the linear form A is (m, d, n) or, with one sample per client, (m, n)."""
    own = own_gradient(cfg)
    if own:
        return own(cfg, data, x, precision)
    loss = load("losses", cfg["problem"])
    A, b, mask = data["A"], data["b"], data["mask"]
    single = A.ndim == 2
    z = product("mn,n->m" if single else "mdn,n->md", A, x, precision)
    d = jnp.maximum(mask, 1.0) if single else \
        jnp.maximum(mask.sum(axis=-1), 1.0)
    per, dz = loss.terms(cfg, z, b)
    per, dz = mask * per, mask * dz
    reg_f, reg_g = loss.regulariser(cfg, x, d)
    if single:
        f = per / d + reg_f
        g = scale_rows(A, dz, precision) / d[:, None] + reg_g
    else:
        f = per.sum(axis=-1) / d + reg_f
        g = product("mdn,md->mn", A, dz, precision) / d[:, None] + reg_g
    return f, g


def masks(sel: dict, m: int):
    """(init, next) of the round's participant mask, from the seeds alone.

    kind "uniform": a key split every round, |C| of a permutation;
    kind "internal": the algorithm's own §V.B draw, from the state's rng
    key folded with the round index."""
    n_sel = sel["n_selected"]

    def draw(key):
        if n_sel >= m:
            return jnp.ones((m,), bool)
        return jax.random.permutation(key, m) < n_sel

    if sel["kind"] == "uniform":
        def nxt(key, t):
            key, sub = jax.random.split(key)
            return draw(sub), key
        return jax.random.PRNGKey(sel["seed"]), nxt
    if sel["kind"] == "internal":
        def nxt(key, t):
            key, sub = jax.random.split(key)
            return draw(jax.random.fold_in(sub, t)), key
        return jax.random.PRNGKey(sel["seed"]), nxt
    raise ValueError(f"unknown participation kind {sel['kind']!r}")


def host_arrays(data: dict):
    """(A, b, mask) as float32; one sample per client as (m, n) rows."""
    A, b, mask = (np.asarray(data[k], np.float32) for k in ("A", "b", "mask"))
    if A.shape[1] == 1:
        A, b, mask = A[:, 0, :], b[:, 0], mask[:, 0]
    return A, b, mask
