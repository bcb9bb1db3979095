"""Fused FedGiA client update (paper eqs (12)-(14) / (15)-(17)).

One elementwise pass over the flattened parameter vector computes the
whole k0-step round in the collapsed closed form (DESIGN §6 B1):

  D    = 1 / (h/m + sigma)           (diagonal H)
  a    = 1 - sigma * D
  base = pi + g
  ADMM branch:  pi' = a^k0 base - g ;  x' = xbar - D a^(k0-1) base
  GD   branch:  pi' = -g           ;  x' = xbar
  both:         z'  = x' + pi'/sigma

The unfused implementation would make ~9 HBM round-trips over model-size
buffers (three updates, k0 times for the scan variant); this kernel makes
4 reads + 3 writes. Memory-bound => the roofline win is the traffic ratio.

Block layout — MXU-free, pure VPU elementwise, lanes of 128 as in the
TPU vector registers:
  single vector: the (N,) stream is viewed as (N/128, 128) and tiled
    (BLOCK_ROWS, 128) a grid step.
  batched: the (mb, N) client state is tiled (BM, BN) a grid step over a
    (cdiv(mb, BM), cdiv(N, BN)) grid, with BM and BN derived from (mb, N)
    alone (`batched_blocks`): at (10^6, 128), 1024 clients a step, 977
    steps. Ragged last blocks are Pallas's masked edge blocks. The (mb,)
    branch select arrives as one (8, 128) int32 tile per client block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 512  # (512, 128) fp32 = 256 KiB per operand block in VMEM
# batched kernel: (BM, BN) blocks of the (mb, N) client state
MAX_BLOCK_LANES = 2048
BLOCK_BYTES = 512 * 1024  # f32 bytes per operand block: 7 streams,
#                           double-buffered, take 7 MiB of VMEM
SEL_ROWS = 8  # (8, 128) int32 select tile: up to 1024 clients a block


def _kernel(sel_ref, scal_ref, xbar_ref, g_ref, pi_ref, h_ref,
            x_out_ref, pi_out_ref, z_out_ref, *, k0: int):
    sigma = scal_ref[0]
    inv_m = scal_ref[1]
    xbar = xbar_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    pi = pi_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)

    d = 1.0 / (h * inv_m + sigma)
    a = 1.0 - sigma * d
    base = pi + g
    ak1 = a ** (k0 - 1)
    pi_admm = ak1 * a * base - g
    x_admm = xbar - d * ak1 * base

    is_sel = sel_ref[0] > 0
    x_new = jnp.where(is_sel, x_admm, xbar)
    pi_new = jnp.where(is_sel, pi_admm, -g)
    z_new = x_new + pi_new / sigma

    x_out_ref[...] = x_new.astype(x_out_ref.dtype)
    pi_out_ref[...] = pi_new.astype(pi_out_ref.dtype)
    z_out_ref[...] = z_new.astype(z_out_ref.dtype)


def _batched_kernel(sel_ref, scal_ref, xbar_ref, g_ref, pi_ref, h_ref,
                    x_out_ref, pi_out_ref, z_out_ref, *, k0: int):
    """One (client block, column block) grid step of the batched update.

    Each operand block is (BM, BN): BM clients, BN lanes of their state.
    `sel_ref` is this client block's (SEL_ROWS, 128) int32 select tile,
    client c of the block at [c // 128, c % 128]; its transpose puts
    clients on sublanes, so column s selects the block's s-th run of 128
    clients, broadcast along lanes. Per element the math is `_kernel`'s,
    in the same order."""
    sigma = scal_ref[0]
    inv_m = scal_ref[1]
    sel_t = sel_ref[...].T  # (128, SEL_ROWS): client s*128 + r at [r, s]
    bm = xbar_ref.shape[0]
    for s in range(pl.cdiv(bm, LANES)):
        rows = pl.ds(s * LANES, min(LANES, bm - s * LANES))
        is_sel = sel_t[:rows.size, s:s + 1] > 0
        xbar = xbar_ref[rows, :].astype(jnp.float32)
        g = g_ref[rows, :].astype(jnp.float32)
        pi = pi_ref[rows, :].astype(jnp.float32)
        h = h_ref[rows, :].astype(jnp.float32)

        d = 1.0 / (h * inv_m + sigma)
        a = 1.0 - sigma * d
        base = pi + g
        ak1 = a ** (k0 - 1)
        pi_admm = ak1 * a * base - g
        x_admm = xbar - d * ak1 * base

        x_new = jnp.where(is_sel, x_admm, xbar)
        pi_new = jnp.where(is_sel, pi_admm, -g)
        z_new = x_new + pi_new / sigma

        x_out_ref[rows, :] = x_new.astype(x_out_ref.dtype)
        pi_out_ref[rows, :] = pi_new.astype(pi_out_ref.dtype)
        z_out_ref[rows, :] = z_new.astype(z_out_ref.dtype)


def batched_blocks(mb: int, n: int) -> tuple[int, int]:
    """The (BM, BN) operand block of the batched kernel at state (mb, n).

    BN: all n lanes up to MAX_BLOCK_LANES, else that many (a multiple of
    128, so a wide state tiles its columns). BM: enough clients for
    BLOCK_BYTES of f32 per operand block, a multiple of 32 (the sublane
    tile of every dtype down to 8 bits) and at most SEL_ROWS * 128, the
    clients one select tile holds; all mb clients where they fit.

    A state that fits one block is cut in two, by clients (from 64) or
    else by columns: a one-step grid copies in, computes and copies out
    in turn, two steps let the pipeline overlap them."""
    if n % LANES:
        raise ValueError(f"state width {n} is not a multiple of {LANES}")
    bn = min(n, MAX_BLOCK_LANES)
    cap = min(SEL_ROWS * LANES, max(32, BLOCK_BYTES // (4 * bn) // 32 * 32))
    bm = min(mb, cap)
    if (bm, bn) == (mb, n):
        if mb >= 64:
            bm = pl.cdiv(mb, 64) * 32
        elif n > LANES:
            bn = pl.cdiv(n, 2 * LANES) * LANES
    return bm, bn


# Flattened pallas_call inputs are (sel, scal, xbar, gbar, pi, h) =
# indices 0..5 and outputs (x', pi', z') = 0..2. The donated path aliases
# the three model-size input streams onto the shape/dtype-matched outputs
# so the collapsed update writes the (m, N) state in place:
#   x'  <- xbar   (the anchor buffer becomes the new client params)
#   pi' <- pi     (the multiplier updates in place)
#   z'  <- gbar   (the 1/m-scaled gradient buffer becomes the new z)
_DONATE_ALIASES = {2: 0, 4: 1, 3: 2}


def _batched_call(xbar, gbar, pi, h, sel, sigma, m, *, k0: int,
                  interpret: bool, donate: bool):
    mb, n = xbar.shape
    bm, bn = batched_blocks(mb, n)
    nb = pl.cdiv(mb, bm)
    grid = (nb, pl.cdiv(n, bn))

    scal = jnp.stack([sigma.astype(jnp.float32), jnp.float32(1.0 / m)])
    # one (SEL_ROWS, 128) lane-dense int32 tile of selects per client
    # block: 4 KiB for up to 1024 clients, where an (mb, 1) column would
    # pad each client to 128 lanes
    sel_arr = jnp.pad(sel.astype(jnp.int32), (0, nb * bm - mb))
    sel_arr = jnp.pad(sel_arr.reshape(nb, bm),
                      ((0, 0), (0, SEL_ROWS * LANES - bm)))
    sel_arr = sel_arr.reshape(nb * SEL_ROWS, LANES)

    block = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    sel_block = pl.BlockSpec((SEL_ROWS, LANES), lambda i, j: (i, 0))
    rep = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((mb, n), xbar.dtype)] * 3
    return tuple(pl.pallas_call(
        functools.partial(_batched_kernel, k0=k0),
        grid=grid,
        in_specs=[sel_block, rep, block, block, block, block],
        out_specs=[block, block, block],
        out_shape=out_shape,
        input_output_aliases=_DONATE_ALIASES if donate else {},
        interpret=interpret,
    )(sel_arr, scal, xbar, gbar, pi, h))


@functools.partial(jax.jit, static_argnames=("k0", "interpret"))
def fedgia_update_batched_kernel(xbar, gbar, pi, h, sel, sigma, m, *,
                                 k0: int, interpret: bool = False):
    """Batched flat round update: all inputs (mb, N) with N % 128 == 0
    (ops.py pads); sel: (mb,) bool — client i's ADMM/GD branch select;
    sigma: () f32; m: GLOBAL client count (the 1/m gradient scale).
    Returns (x', pi', z'), each (mb, N).

    Grid is (client blocks, column blocks) of `batched_blocks(mb, N)`:
    one kernel launch covers the whole (m, N) client-state buffer — the
    flat engine's round is a single fused elementwise pass instead of
    per-leaf (or per-client) dispatch.
    """
    return _batched_call(xbar, gbar, pi, h, sel, sigma, m,
                         k0=k0, interpret=interpret, donate=False)


@functools.partial(jax.jit, static_argnames=("k0", "interpret"),
                   donate_argnums=(0, 1, 2))
def fedgia_update_batched_kernel_donated(xbar, gbar, pi, h, sel, sigma, m, *,
                                         k0: int, interpret: bool = False):
    """Donated twin of `fedgia_update_batched_kernel`: the (mb, N) xbar /
    gbar / pi buffers are consumed — `donate_argnums` releases them to XLA
    and `input_output_aliases` maps each onto the matching output (see
    `_DONATE_ALIASES`), so the round update allocates ZERO extra
    model-size temporaries (`memory_analysis()` shows the aliased bytes,
    tests/test_kernels.py). The caller must not reuse the donated arrays
    afterwards (doing so raises — the buffer is genuinely gone); `h` and
    the scalars stay borrowed.
    """
    return _batched_call(xbar, gbar, pi, h, sel, sigma, m,
                         k0=k0, interpret=interpret, donate=True)


@functools.partial(jax.jit, static_argnames=("k0", "interpret"))
def fedgia_update_kernel(xbar, gbar, pi, h, sel, sigma, m, *, k0: int,
                         interpret: bool = False):
    """All inputs (N,) with N % 128 == 0 (ops.py pads); sel: () bool;
    sigma: () f32; m: client count. Returns (x', pi', z')."""
    n = xbar.shape[0]
    rows = n // LANES
    br = min(BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, br),)

    def reshape(v):
        return v.reshape(rows, LANES)

    scal = jnp.stack([sigma.astype(jnp.float32), jnp.float32(1.0 / m)])
    sel_arr = sel.astype(jnp.int32).reshape(1)

    block = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    rep = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((rows, LANES), xbar.dtype)] * 3
    x_new, pi_new, z_new = pl.pallas_call(
        functools.partial(_kernel, k0=k0),
        grid=grid,
        in_specs=[rep, rep, block, block, block, block],
        out_specs=[block, block, block],
        out_shape=out_shape,
        interpret=interpret,
    )(sel_arr, scal, reshape(xbar), reshape(gbar), reshape(pi), reshape(h))
    return x_new.reshape(n), pi_new.reshape(n), z_new.reshape(n)
