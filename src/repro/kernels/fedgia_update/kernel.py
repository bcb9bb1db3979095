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

Block layout: the 1-D parameter stream is viewed as (rows, 128) lanes and
tiled (BLOCK_ROWS, 128) per grid step — MXU-free, pure VPU elementwise,
lane dimension 128 matches the TPU vector registers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_ROWS = 512  # (512, 128) fp32 = 256 KiB per operand block in VMEM
SEL_BLOCK = 1024  # clients per SMEM block of the batched branch select:
#                   int32, 4 KiB of the 1 MiB SMEM


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
    """One (client, row-block) grid step of the batched round update.

    Identical math to `_kernel`, but the client index is grid dimension 0
    and the per-client ADMM/GD branch select comes from the
    (SEL_BLOCK,) SMEM block of the (m,) select vector that holds this
    step's client — the whole round's client axis runs in ONE
    pallas_call instead of m dispatches."""
    i = pl.program_id(0)
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

    is_sel = sel_ref[i % SEL_BLOCK] > 0
    x_new = jnp.where(is_sel, x_admm, xbar)
    pi_new = jnp.where(is_sel, pi_admm, -g)
    z_new = x_new + pi_new / sigma

    x_out_ref[...] = x_new.astype(x_out_ref.dtype)
    pi_out_ref[...] = pi_new.astype(pi_out_ref.dtype)
    z_out_ref[...] = z_new.astype(z_out_ref.dtype)


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
    rows = n // LANES
    br = min(BLOCK_ROWS, rows)
    grid = (mb, pl.cdiv(rows, br))

    def reshape(v):
        return v.reshape(mb, rows, LANES)

    scal = jnp.stack([sigma.astype(jnp.float32), jnp.float32(1.0 / m)])

    block = pl.BlockSpec((1, br, LANES), lambda i, j: (i, j, 0))
    rep = pl.BlockSpec(memory_space=pltpu.SMEM)
    # the select vector enters SMEM one SEL_BLOCK of clients at a time:
    # the whole (mb,) int32 vector exceeds the 1 MiB SMEM from mb ~ 2.6e5
    sel_arr = jnp.pad(sel.astype(jnp.int32), (0, (-mb) % SEL_BLOCK))
    sel_block = pl.BlockSpec((SEL_BLOCK,), lambda i, j: (i // SEL_BLOCK,),
                             memory_space=pltpu.SMEM)
    out_shape = [jax.ShapeDtypeStruct((mb, rows, LANES), xbar.dtype)] * 3
    x_new, pi_new, z_new = pl.pallas_call(
        functools.partial(_batched_kernel, k0=k0),
        grid=grid,
        in_specs=[sel_block, rep, block, block, block, block],
        out_specs=[block, block, block],
        out_shape=out_shape,
        input_output_aliases=_DONATE_ALIASES if donate else {},
        interpret=interpret,
    )(sel_arr, scal, reshape(xbar), reshape(gbar), reshape(pi), reshape(h))
    return (x_new.reshape(mb, n), pi_new.reshape(mb, n),
            z_new.reshape(mb, n))


@functools.partial(jax.jit, static_argnames=("k0", "interpret"))
def fedgia_update_batched_kernel(xbar, gbar, pi, h, sel, sigma, m, *,
                                 k0: int, interpret: bool = False):
    """Batched flat round update: all inputs (mb, N) with N % 128 == 0
    (ops.py pads); sel: (mb,) bool — client i's ADMM/GD branch select;
    sigma: () f32; m: GLOBAL client count (the 1/m gradient scale).
    Returns (x', pi', z'), each (mb, N).

    Grid is (clients, row blocks): one kernel launch covers the whole
    (m, N) client-state buffer — the flat engine's round is a single
    fused elementwise pass instead of per-leaf (or per-client) dispatch.
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
