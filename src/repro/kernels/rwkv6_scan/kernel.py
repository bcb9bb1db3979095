"""RWKV-6 recurrence, chunked over time with the state resident in VMEM.

  y_t = r_t · (S_{t-1} + diag(u) k_t v_t^T)
  S_t = diag(w_t) S_{t-1} + k_t v_t^T

The XLA lax.scan lowering round-trips the (hd x hd) state through HBM every
timestep; here the state stays in a VMEM scratch for the whole sequence
while (r,k,v,w) stream through in (1, 1, block_t, hd) tiles — grid
(B, H, T/block_t) with the time dimension sequential. Per-step work is a
rank-1 update + matvec on (hd, hd) = (64, 64): VPU/MXU friendly.

TPU adaptation of the CUDA chunked-WKV kernel from the RWKV repo: the
shared-memory per-warp state becomes a VMEM scratch per (batch, head) grid
cell; warp-level parallelism over heads becomes grid parallelism.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 64

def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sfin_ref, s_scr, *,
            block_t: int, seq_len: int):
    ti = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    u = u_ref[0].astype(jnp.float32)  # (hd,)

    def step(t, S):
        rt = r_ref[0, 0, t].astype(jnp.float32)  # (hd,)
        kt = k_ref[0, 0, t].astype(jnp.float32)
        vt = v_ref[0, 0, t].astype(jnp.float32)
        wt = w_ref[0, 0, t].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]  # (hdk, hdv)
        # y_t = (r·(u*k)) v + r @ S
        y = jnp.sum(rt * u * kt) * vt + jax.lax.dot_general(
            rt[None, :], S, (((1,), (0,)), ((), ()))
        ).reshape(-1)
        y_ref[0, 0, t] = y.astype(y_ref.dtype)
        return wt[:, None] * S + kv

    S = jax.lax.fori_loop(0, block_t, step, s_scr[...])
    s_scr[...] = S

    @pl.when(ti == nt - 1)
    def _fin():
        sfin_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_scan_kernel(r, k, v, w, u, *, block_t: int = DEFAULT_BLOCK_T,
                      interpret: bool = False):
    """r,k,v,w: (B,H,T,hd); u: (H,hd). Returns (y (B,H,T,hd), S (B,H,hd,hd))."""
    B, H, T, hd = r.shape
    block_t = min(block_t, T)
    pad = (-T) % block_t
    if pad:
        padt = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
        r, k, v = padt(r), padt(k), padt(v)
        # pad decay with ones so the state is unchanged on padded steps
        w = jnp.pad(w, ((0, 0), (0, 0), (0, pad), (0, 0)), constant_values=1.0)
    Tp = T + pad

    grid = (B, H, Tp // block_t)
    seq_spec = pl.BlockSpec((1, 1, block_t, hd), lambda b, h, t: (b, h, t, 0))
    u_spec = pl.BlockSpec((1, hd), lambda b, h, t: (h, 0))
    y_spec = seq_spec
    s_spec = pl.BlockSpec((1, 1, hd, hd), lambda b, h, t: (b, h, 0, 0))

    y, s_fin = pl.pallas_call(
        functools.partial(_kernel, block_t=block_t, seq_len=T),
        grid=grid,
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, u_spec],
        out_specs=[y_spec, s_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, hd), r.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(r, k, v, w, u)
    return y[:, :, :T], s_fin
