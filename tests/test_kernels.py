"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fedgia_update import (
    fedgia_update,
    fedgia_update_flat,
    fedgia_update_ref,
)
from repro.kernels.fedgia_update.kernel import (
    LANES,
    MAX_BLOCK_LANES,
    batched_blocks,
    fedgia_update_kernel,
)
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.rwkv6_scan import rwkv6_scan, rwkv6_scan_ref

RNG = np.random.default_rng(42)


# ------------------------------------------------------------- fedgia_update
@pytest.mark.parametrize("n", [64, 128, 1000, 40000])
@pytest.mark.parametrize("k0", [1, 4, 9])
@pytest.mark.parametrize("sel", [True, False])
def test_fedgia_update_matches_unrolled(n, k0, sel):
    xbar = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    g = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    pi = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    h = jnp.asarray(RNG.uniform(0.05, 3.0, n), jnp.float32)
    sigma = jnp.float32(0.7)
    ref = fedgia_update_ref(xbar, g, pi, h, jnp.asarray(sel), sigma, 8, k0=k0)
    out = fedgia_update(xbar, g, pi, h, sel, sigma, 8, k0=k0, interpret=True)
    for a, b, name in zip(out, ref, ("x", "pi", "z")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5,
            err_msg=f"{name} mismatch n={n} k0={k0} sel={sel}",
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedgia_update_dtypes(dtype):
    n = 512
    args = [jnp.asarray(RNG.standard_normal(n), dtype) for _ in range(3)]
    h = jnp.asarray(RNG.uniform(0.1, 1.0, n), dtype)
    sigma = jnp.float32(0.5)
    ref = fedgia_update_ref(*args, h, jnp.asarray(True), sigma, 4, k0=5)
    out = fedgia_update(*args, h, True, sigma, 4, k0=5, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for a, b in zip(out, ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=tol, atol=tol
        )


@pytest.mark.parametrize(
    "n", [2 * LANES, 2 * LANES + 1, 3 * LANES - 1],
    ids=["mod0", "mod1", "modLANES-1"],
)
def test_fedgia_update_padding_edges(n):
    """N % LANES in {0, 1, LANES-1}: the ops-layer lane padding must be
    invisible — kernel (interpret) == unpadded jnp oracle."""
    xbar = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    g = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    pi = jnp.asarray(RNG.standard_normal(n), jnp.float32)
    h = jnp.asarray(RNG.uniform(0.05, 3.0, n), jnp.float32)
    sigma = jnp.float32(0.6)
    ref = fedgia_update_ref(xbar, g, pi, h, jnp.asarray(True), sigma, 8, k0=4)
    out = fedgia_update(xbar, g, pi, h, True, sigma, 8, k0=4, interpret=True)
    for a, b, name in zip(out, ref, ("x", "pi", "z")):
        assert a.shape == (n,), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("n", [LANES, LANES + 1, 2 * LANES - 1])
@pytest.mark.parametrize("k0", [1, 5])
def test_fedgia_update_batched_matches_ref(n, k0):
    """The batched (m, N) kernel — the flat engine's round update — equals
    the jnp oracle per client, mixed ADMM/GD branch selects, across the
    same padding edges."""
    m = 6
    xbar = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    pi = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    h = jnp.asarray(RNG.uniform(0.05, 3.0, (m, n)), jnp.float32)
    sel = jnp.asarray([True, False, True, True, False, True])
    sigma = jnp.float32(0.7)
    ref = fedgia_update_flat(xbar, g, pi, h, sel, sigma, m, k0=k0,
                             use_kernel=False)
    out = fedgia_update_flat(xbar, g, pi, h, sel, sigma, m, k0=k0,
                             use_kernel=True, interpret=True)
    for a, b, name in zip(out, ref, ("x", "pi", "z")):
        assert a.shape == (m, n), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5, err_msg=name)


# client blocks at N = 128 lanes, and at the widest column block
BM_128 = batched_blocks(10**6, LANES)[0]
BM_WIDE = batched_blocks(10**6, MAX_BLOCK_LANES)[0]


def _every_third(m):
    return np.arange(m) % 3 == 0


def _random_half(m):
    return RNG.random(m) < 0.5


@pytest.mark.parametrize("m,n,pick", [
    (2 * BM_128 + 5, LANES, _random_half),
    (BM_128 + 130, LANES, _every_third),
    (1003, LANES, _every_third),
    (10, MAX_BLOCK_LANES + 3 * LANES, _random_half),
    (2 * BM_WIDE + 5, MAX_BLOCK_LANES + 3 * LANES, _every_third),
], ids=["ragged_clients", "sel_inside_subblock", "unaligned_clients",
        "ragged_columns", "ragged_both"])
def test_fedgia_update_batched_tiles(m, n, pick):
    """The (BM, BN) tiling: ragged last client and column blocks, and a
    select that changes inside one 128-client run, read each client's
    own select bit. Matches the jnp oracle, and each row equals the
    single-vector kernel on that client bit for bit."""
    xbar, g, pi = (jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
                   for _ in range(3))
    h = jnp.asarray(RNG.uniform(0.05, 3.0, (m, n)), jnp.float32)
    sel = jnp.asarray(pick(m))
    sigma = jnp.float32(0.7)
    ref = fedgia_update_flat(xbar, g, pi, h, sel, sigma, m, k0=3,
                             use_kernel=False)
    out = fedgia_update_flat(xbar, g, pi, h, sel, sigma, m, k0=3,
                             use_kernel=True, interpret=True)
    single = jax.vmap(lambda *v: fedgia_update_kernel(
        *v, sigma, m, k0=3, interpret=True))(xbar, g, pi, h, sel)
    for a, b, c, name in zip(out, ref, single, ("x", "pi", "z")):
        assert a.shape == (m, n), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                      err_msg=name)


@pytest.mark.parametrize("mb,n", [
    (10**6, 128), (10**6 + 3, 128), (128, 128), (128, 1024), (6, 256),
    (1003, 128), (200, 384), (8, 2**20), (2**14, 2**16), (133, 2432),
    (6, 128), (64, 128), (40, 384),
])
def test_fedgia_update_batched_block_rule(mb, n):
    """`batched_blocks` tiles lane-aligned columns, takes BM a multiple
    of the sublane tile (or all mb clients) and within one select tile,
    keeps the seven double-buffered streams under 16 MiB of VMEM, and
    gives the pipeline two steps or more where the state can be cut."""
    bm, bn = batched_blocks(mb, n)
    assert bn % LANES == 0 and bn <= min(n, MAX_BLOCK_LANES)
    assert bm == mb or (bm % 32 == 0 and bm < mb)
    assert bm <= 8 * LANES
    assert 7 * 2 * bm * bn * 4 + 2 * 8 * LANES * 4 < 16 * 2**20
    steps = -(-mb // bm) * -(-n // bn)
    assert steps >= 2 or (mb < 64 and n == LANES)
    if (mb, n) == (10**6, 128):
        assert steps <= 2000


def test_fedgia_update_batched_block_rule_needs_lanes():
    with pytest.raises(ValueError, match="multiple of 128"):
        batched_blocks(8, LANES + 1)


def _donation_args(m=6, n=2 * LANES):
    xbar = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    pi = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    h = jnp.asarray(RNG.uniform(0.05, 3.0, (m, n)), jnp.float32)
    sel = jnp.asarray([True, False, True, True, False, True][:m])
    return xbar, g, pi, h, sel, jnp.float32(0.7), m


def test_fedgia_update_donated_bitwise_equals_undonated():
    """Donation aliases buffers; it must not change a single bit of the
    math (interpret mode on CPU; `+ 0` copies keep the originals alive
    for the comparison)."""
    xbar, g, pi, h, sel, sigma, m = _donation_args()
    ref = fedgia_update_flat(xbar, g, pi, h, sel, sigma, m, k0=3,
                             use_kernel=True, interpret=True, donate=False)
    out = fedgia_update_flat(xbar + 0, g + 0, pi + 0, h, sel, sigma, m,
                             k0=3, use_kernel=True, interpret=True,
                             donate=True)
    for a, b, name in zip(out, ref, ("x", "pi", "z")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_fedgia_update_donated_consumes_buffers():
    """The donated entry point genuinely consumes xbar/gbar/pi: a second
    call on the same (now-deleted) arrays must raise instead of silently
    reading stale memory."""
    from repro.kernels.fedgia_update import fedgia_update_batched_kernel_donated

    xbar, g, pi, h, sel, sigma, m = _donation_args()
    xb, gb, pb = xbar + 0, g + 0, pi + 0
    fedgia_update_batched_kernel_donated(xb, gb, pb, h, sel, sigma,
                                         jnp.int32(m), k0=3, interpret=True)
    with pytest.raises((RuntimeError, ValueError),
                       match="deleted|donated"):
        fedgia_update_batched_kernel_donated(xb, gb, pb, h, sel, sigma,
                                             jnp.int32(m), k0=3,
                                             interpret=True)


def test_fedgia_update_donated_memory_analysis_aliases():
    """`memory_analysis()` proof of the in-place contract: the donated
    program aliases all three (m, N) state streams onto its outputs
    (alias bytes == 3 * m * N * 4) and allocates NO extra temp relative
    to the undonated lowering of the same call."""
    from repro.kernels.fedgia_update import (
        fedgia_update_batched_kernel,
        fedgia_update_batched_kernel_donated,
    )

    xbar, g, pi, h, sel, sigma, m = _donation_args()
    n = xbar.shape[1]
    args = (xbar, g, pi, h, sel, sigma, jnp.int32(m))
    don = fedgia_update_batched_kernel_donated.lower(
        *args, k0=3, interpret=True).compile().memory_analysis()
    und = fedgia_update_batched_kernel.lower(
        *args, k0=3, interpret=True).compile().memory_analysis()
    assert don.alias_size_in_bytes == 3 * m * n * 4
    assert und.alias_size_in_bytes == 0
    assert don.temp_size_in_bytes <= und.temp_size_in_bytes


def test_fedgia_update_flat_donate_falls_back_when_padded():
    """A ragged N forces a lane-padding copy, which would break the alias
    — ops.py must silently route donate=True through the undonated
    kernel (correct results, originals still alive)."""
    m, n = 4, LANES + 3
    xbar = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    pi = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    h = jnp.asarray(RNG.uniform(0.1, 2.0, (m, n)), jnp.float32)
    sel = jnp.asarray([True, True, False, True])
    sigma = jnp.float32(0.5)
    ref = fedgia_update_flat(xbar, g, pi, h, sel, sigma, m, k0=2,
                             use_kernel=True, interpret=True, donate=False)
    out = fedgia_update_flat(xbar, g, pi, h, sel, sigma, m, k0=2,
                             use_kernel=True, interpret=True, donate=True)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the originals survived (no donation happened on the padded path)
    assert np.isfinite(np.asarray(xbar)).all()


def test_fedgia_update_batched_rowwise_equals_single():
    """Each row of the batched kernel equals the single-vector kernel on
    that client's slice bit for bit (same per-element math, same order)."""
    m, n = 4, 2 * LANES
    xbar = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    pi = jnp.asarray(RNG.standard_normal((m, n)), jnp.float32)
    h = jnp.asarray(RNG.uniform(0.1, 2.0, (m, n)), jnp.float32)
    sel = jnp.asarray([True, False, True, False])
    sigma = jnp.float32(0.4)
    batched = fedgia_update_flat(xbar, g, pi, h, sel, sigma, m, k0=3,
                                 use_kernel=True, interpret=True)
    for i in range(m):
        single = fedgia_update(xbar[i], g[i], pi[i], h[i], bool(sel[i]),
                               sigma, m, k0=3, interpret=True)
        for a, b, name in zip(batched, single, ("x", "pi", "z")):
            np.testing.assert_array_equal(np.asarray(a[i]), np.asarray(b),
                                          err_msg=f"client {i} {name}")


# ------------------------------------------------------------ flash_attention
@pytest.mark.parametrize(
    "B,H,Kv,S,hd,window,bq,bk",
    [
        (2, 4, 4, 128, 64, None, 64, 64),
        (1, 8, 2, 200, 64, None, 64, 64),   # GQA, unaligned seq
        (2, 4, 1, 192, 128, None, 128, 64), # MQA
        (1, 4, 4, 256, 64, 64, 64, 64),     # sliding window
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, H, Kv, S, hd, window, bq, bk, dtype):
    q = jnp.asarray(RNG.standard_normal((B, H, S, hd)), dtype)
    k = jnp.asarray(RNG.standard_normal((B, Kv, S, hd)), dtype)
    v = jnp.asarray(RNG.standard_normal((B, Kv, S, hd)), dtype)
    ref = flash_attention_ref(q, k, v, window=window)
    out = flash_attention(q, k, v, window=window, interpret=True,
                          block_q=bq, block_k=bk)
    tol = 2e-5 if dtype == jnp.float32 else 2.5e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_flash_attention_matches_model_blocked_softmax():
    """The kernel and models/attention.blocked_attention agree (same oracle)."""
    from repro.models.attention import blocked_attention

    B, H, Kv, S, hd = 1, 4, 2, 96, 32
    q = jnp.asarray(RNG.standard_normal((B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((B, S, Kv, hd)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((B, S, Kv, hd)), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)
    ref = blocked_attention(q, k, v, pos, pos, block_k=32)
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        interpret=True, block_q=32, block_k=32,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- rwkv6_scan
@pytest.mark.parametrize(
    "B,H,T,hd,bt",
    [(2, 3, 64, 32, 32), (1, 4, 100, 64, 64), (2, 2, 128, 64, 16)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan_sweep(B, H, T, hd, bt, dtype):
    r = jnp.asarray(RNG.standard_normal((B, H, T, hd)) * 0.5, dtype)
    k = jnp.asarray(RNG.standard_normal((B, H, T, hd)) * 0.5, dtype)
    v = jnp.asarray(RNG.standard_normal((B, H, T, hd)) * 0.5, dtype)
    w = jnp.asarray(RNG.uniform(0.85, 0.999, (B, H, T, hd)), jnp.float32)
    u = jnp.asarray(RNG.standard_normal((H, hd)) * 0.5, jnp.float32)
    yr, sr = rwkv6_scan_ref(r, k, v, w, u)
    yk, sk = rwkv6_scan(r, k, v, w, u, interpret=True, block_t=bt)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(yk, np.float32), np.asarray(yr, np.float32), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-4, atol=1e-3)


def test_rwkv6_state_carry_is_chunk_invariant():
    """Final state must not depend on the chunk size."""
    B, H, T, hd = 1, 2, 96, 32
    r, k, v = (jnp.asarray(RNG.standard_normal((B, H, T, hd)) * 0.3, jnp.float32)
               for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.9, 0.999, (B, H, T, hd)), jnp.float32)
    u = jnp.asarray(RNG.standard_normal((H, hd)) * 0.3, jnp.float32)
    _, s16 = rwkv6_scan(r, k, v, w, u, interpret=True, block_t=16)
    _, s48 = rwkv6_scan(r, k, v, w, u, interpret=True, block_t=48)
    np.testing.assert_allclose(np.asarray(s16), np.asarray(s48), rtol=1e-5, atol=1e-5)
