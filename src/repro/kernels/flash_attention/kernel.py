"""Blocked causal GQA flash attention for TPU.

Grid (B, H, num_q_blocks, num_kv_blocks); the innermost kv dimension is
sequential ("arbitrary") so the running max / denominator / accumulator
live in VMEM scratch across kv steps — the streaming-softmax algorithm.
GQA is expressed in the BlockSpec index map: the kv block for query head h
is head h // group_size, so K/V are never materialised per-query-head.

VMEM budget per step (fp32): q (bq,hd) + k,v (bk,hd) + scores (bq,bk)
+ acc (bq,hd): with bq=bk=256, hd=128 that is ~0.7 MiB — comfortably
within a v5e core's VMEM while double-buffering.

Causal + optional sliding-window masking is computed from block indices;
fully-masked kv blocks are skipped via pl.when (no MXU work for the upper
triangle — ~2x prefill win).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256

def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, seq_len: int,
            window, causal: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # visibility of this kv block for this q block
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if window is not None:
        needed = jnp.logical_and(
            needed, k_start + block_k - 1 >= q_start - (window - 1)
        )

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ()))
        )  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_len
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ()))
        )
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention_kernel(q, k, v, *, causal: bool = True, window=None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False):
    """q: (B,H,S,hd); k,v: (B,Kv,T,hd) with H % Kv == 0 and S == T.
    Returns (B,H,S,hd)."""
    B, H, S, hd = q.shape
    Kv, T = k.shape[1], k.shape[2]
    G = H // Kv
    scale = 1.0 / (hd**0.5)

    block_q = min(block_q, S)
    block_k = min(block_k, T)
    # pad sequence to block multiples (masked out via seq_len)
    pad_q = (-S) % block_q
    pad_k = (-T) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Sp, Tp = S + pad_q, T + pad_k

    grid = (B, H, Sp // block_q, Tp // block_k)
    q_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, block_k, hd), lambda b, h, iq, ik: (b, h // G, ik, 0)
    )
    o_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0))

    out = pl.pallas_call(
        functools.partial(
            _kernel,
            scale=scale,
            block_q=block_q,
            block_k=block_k,
            seq_len=T,
            window=window,
            causal=causal,
        ),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S]
