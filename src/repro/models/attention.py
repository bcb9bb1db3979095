"""Attention: GQA (llama-style, optional QKV bias / sliding window) and MLA
(DeepSeek-V3 latent attention, absorbed decode path).

The softmax is computed with the *blocked streaming* (flash) algorithm in
pure jnp — numerically identical to full softmax, O(S * block_k) memory.
This is both the production lowering used by the dry-run and the oracle for
the Pallas flash kernel in repro/kernels/flash_attention.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import apply_rope, he_init, yarn_inv_freq

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnMode:
    kind: str = "train"  # train | prefill | decode
    window: Optional[int] = None  # sliding-window mask width (None = full)
    block_k: int = 512


# ============================================================ blocked softmax
def blocked_attention(q, k, v, q_positions, kv_positions, *, window=None,
                      block_k=512, scale=None, unroll=False):
    """Streaming-softmax attention.

    q: (B, S, H, dqk); k: (B, T, Kv, dqk); v: (B, T, Kv, dv)
    q_positions: (S,) int32 absolute positions of queries
    kv_positions: (T,) int32 absolute positions of keys (-1 = invalid slot)
    Causal: key visible iff 0 <= kv_pos <= q_pos (and q_pos - kv_pos < window).
    Returns (B, S, H, dv).
    """
    B, S, H, dqk = q.shape
    T, Kv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // Kv
    scale = scale if scale is not None else 1.0 / (dqk**0.5)

    qr = q.reshape(B, S, Kv, G, dqk).transpose(0, 2, 3, 1, 4)  # B,Kv,G,S,dqk
    qr = (qr * scale).astype(q.dtype)

    block_k = min(block_k, T)
    nb = -(-T // block_k)
    pad = nb * block_k - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-1)
    kb = k.reshape(B, nb, block_k, Kv, dqk).transpose(1, 0, 3, 2, 4)  # nb,B,Kv,bk,d
    vb = v.reshape(B, nb, block_k, Kv, dv).transpose(1, 0, 3, 2, 4)
    pb = kv_positions.reshape(nb, block_k)

    def step(carry, xs):
        m, l, acc = carry
        kblk, vblk, posblk = xs
        s = jnp.einsum(
            "bkgsd,bktd->bkgst", qr.astype(jnp.float32), kblk.astype(jnp.float32)
        )  # B,Kv,G,S,bk
        valid = (posblk[None, :] <= q_positions[:, None]) & (posblk[None, :] >= 0)
        if window is not None:
            valid &= q_positions[:, None] - posblk[None, :] < window
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgst,bktd->bkgsd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Kv, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Kv, G, S), jnp.float32)
    acc0 = jnp.zeros((B, Kv, G, S, dv), jnp.float32)
    if unroll:
        # straight-line variant for the dry-run cost pass: XLA cost_analysis
        # counts scan bodies once, so the streaming loop must be unrolled
        # for faithful FLOP/byte accounting.
        carry = (m0, l0, acc0)
        for i in range(nb):
            carry, _ = step(carry, (kb[i], vb[i], pb[i]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, dv)
    return out.astype(q.dtype)


# ============================================ banded causal attention
# Training-mode attention over one sequence with no cache: causal, and
# within `window` keys where a window is given (a key is visible iff
# 0 <= q - k < window). Queries and keys go in blocks of `block`; query
# block i visits only key blocks lo(i)..i, the ones its causal band can
# see, in the forward pass and in the gradient, which is written out
# (FlashAttention-2's: scores recomputed per block pair, from the
# saved log-sum-exp) rather than taken through the loops. Products take
# the inputs' dtype and accumulate in float32; the softmax is float32.


def _first_key_block(i, block: int, window: Optional[int]):
    if window is None:
        return jnp.zeros_like(i)
    return jnp.maximum(0, (i * block - window + 1) // block)


def _visible(i, j, block: int, window: Optional[int]):
    """(block, block) mask of query block i against key block j."""
    r = jnp.arange(block)
    d = (i * block + r)[:, None] - (j * block + r)[None, :]
    ok = d >= 0
    if window is not None:
        ok &= d < window
    return ok


def _to_blocks(q, k, v, block: int):
    """q (B,S,H,D) -> (nb,B,Kv,G,b,D); k, v (B,S,Kv,D) -> (nb,B,Kv,b,D),
    S padded up to a whole number of blocks."""
    B, S, H, D = q.shape
    Kv = k.shape[2]
    nb = -(-S // block)
    pad = ((0, 0), (0, nb * block - S), (0, 0), (0, 0))
    q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    qb = q.reshape(B, nb, block, Kv, H // Kv, D).transpose(1, 0, 3, 4, 2, 5)
    kb = k.reshape(B, nb, block, Kv, D).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(B, nb, block, Kv, D).transpose(1, 0, 3, 2, 4)
    return qb, kb, vb


def _from_blocks(ob, S: int):
    """(nb,B,Kv,G,b,D) -> (B,S,H,D)."""
    nb, B, Kv, G, b, D = ob.shape
    out = ob.transpose(1, 0, 4, 2, 3, 5).reshape(B, nb * b, Kv * G, D)
    return out[:, :S]


def _scores(qi, kj, scale):
    return jnp.einsum("bkgqd,bktd->bkgqt", qi, kj,
                      preferred_element_type=jnp.float32) * scale


def _banded_fwd_impl(q, k, v, window, block, scale):
    qb, kb, vb = _to_blocks(q, k, v, block)
    nb = qb.shape[0]

    def one(args):
        i, qi = args

        def body(j, carry):
            m, l, acc = carry
            s = _scores(qi, kb[j], scale)
            ok = _visible(i, j, block, window)
            s = jnp.where(ok, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            corr = jnp.exp(m - m_new)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,bktd->bkgqd", p.astype(v.dtype), vb[j],
                preferred_element_type=jnp.float32)
            return m_new, l * corr + p.sum(-1), acc

        stat = qi.shape[:-1]
        m, l, acc = jax.lax.fori_loop(
            _first_key_block(i, block, window), i + 1, body,
            (jnp.full(stat, NEG_INF, jnp.float32),
             jnp.zeros(stat, jnp.float32),
             jnp.zeros(qi.shape, jnp.float32)))
        return (acc / l[..., None]).astype(q.dtype), m + jnp.log(l)

    ob, lse = jax.lax.map(one, (jnp.arange(nb), qb))
    return ob, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def banded_attention(q, k, v, window: Optional[int], block: int,
                     scale: float):
    """q: (B,S,H,D); k, v: (B,S,Kv,D), H a multiple of Kv (GQA); the
    positions are 0..S-1. Returns (B,S,H,D) in q's dtype."""
    ob, _ = _banded_fwd_impl(q, k, v, window, block, scale)
    return _from_blocks(ob, q.shape[1])


def _banded_fwd(q, k, v, window, block, scale):
    ob, lse = _banded_fwd_impl(q, k, v, window, block, scale)
    return _from_blocks(ob, q.shape[1]), (q, k, v, ob, lse)


def _banded_bwd(window, block, scale, res, dout):
    q, k, v, ob, lse = res
    S = q.shape[1]
    qb, kb, vb = _to_blocks(q, k, v, block)
    dob, _, _ = _to_blocks(dout, k, v, block)
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32), -1)
    nb = qb.shape[0]

    def one(carry, xs):
        i, qi, doi, lsei, di = xs

        def body(j, c):
            dq, dk, dv = c
            kj, vj = kb[j], vb[j]
            ok = _visible(i, j, block, window)
            p = jnp.where(ok, jnp.exp(_scores(qi, kj, scale)
                                      - lsei[..., None]), 0.0)
            dp = jnp.einsum("bkgqd,bktd->bkgqt", doi, vj,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - di[..., None])).astype(q.dtype)
            dq = dq + scale * jnp.einsum("bkgqt,bktd->bkgqd", ds, kj,
                                         preferred_element_type=jnp.float32)
            dk = dk.at[j].add(scale * jnp.einsum(
                "bkgqt,bkgqd->bktd", ds, qi,
                preferred_element_type=jnp.float32))
            dv = dv.at[j].add(jnp.einsum(
                "bkgqt,bkgqd->bktd", p.astype(v.dtype), doi,
                preferred_element_type=jnp.float32))
            return dq, dk, dv

        dq, dk, dv = jax.lax.fori_loop(
            _first_key_block(i, block, window), i + 1, body,
            (jnp.zeros(qi.shape, jnp.float32),) + carry)
        return (dk, dv), dq.astype(q.dtype)

    zeros = jnp.zeros(kb.shape, jnp.float32)
    (dk, dv), dqb = jax.lax.scan(one, (zeros, zeros),
                                 (jnp.arange(nb), qb, dob, lse, delta))
    B, Kv = k.shape[0], k.shape[2]
    unblock = lambda a: a.transpose(1, 0, 3, 2, 4).reshape(
        B, -1, Kv, a.shape[-1])[:, :S].astype(k.dtype)
    return _from_blocks(dqb, S), unblock(dk), unblock(dv)


banded_attention.defvjp(_banded_fwd, _banded_bwd)


# ===================================================================== GQA
def gqa_init(rng, cfg: ModelConfig, dtype):
    d, H, Kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": he_init(ks[0], (d, H * hd), d, dtype),
        "wk": he_init(ks[1], (d, Kv * hd), d, dtype),
        "wv": he_init(ks[2], (d, Kv * hd), d, dtype),
        "wo": he_init(ks[3], (H * hd, d), H * hd, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((Kv * hd,), dtype)
        p["bv"] = jnp.zeros((Kv * hd,), dtype)
    return p


def init_gqa_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    Kv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, cache_len, Kv, hd), dtype),
        "v": jnp.zeros((batch, cache_len, Kv, hd), dtype),
        "slot_pos": jnp.full((cache_len,), -1, jnp.int32),
        "pos": jnp.zeros((), jnp.int32),
    }


def _write_cache(cache, k_new, v_new, positions):
    """Ring-buffer write: entries land at position % W. positions: (S,).
    When S > W only the LAST W entries are written (unique slots — a
    wrapped scatter with duplicate indices has undefined write order)."""
    W = cache["k"].shape[1]
    S = k_new.shape[1]
    if S > W:
        k_new, v_new, positions = k_new[:, -W:], v_new[:, -W:], positions[-W:]
    idx = positions % W
    cache = dict(cache)
    cdt = cache["k"].dtype  # supports quantized (fp8) caches
    cache["k"] = cache["k"].at[:, idx].set(k_new.astype(cdt))
    cache["v"] = cache["v"].at[:, idx].set(v_new.astype(cdt))
    cache["slot_pos"] = cache["slot_pos"].at[idx].set(positions)
    cache["pos"] = positions[-1] + 1
    return cache


def gqa_apply(params, cfg: ModelConfig, x, positions, cache, mode: AttnMode,
              kind: Optional[str] = None):
    """x: (B,S,d); positions: (S,) int32. Returns (out, new_cache).

    `kind` ("window" | "full") is the layer's place in `cfg.layer_types`:
    window layers see `cfg.sliding_window` keys with plain RoPE, full
    layers every earlier key with YaRN's RoPE where `cfg.yarn_factor` is
    set; both run `banded_attention` (training only, positions 0..S-1)."""
    B, S, d = x.shape
    H, Kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, params["wq"])
    k = jnp.einsum("bsd,de->bse", x, params["wk"])
    v = jnp.einsum("bsd,de->bse", x, params["wv"])
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Kv, hd)
    v = v.reshape(B, S, Kv, hd)
    if kind is not None:
        return _patterned_attention(params, cfg, q, k, v, positions, mode,
                                    kind), cache
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    unroll = not cfg.scan_layers
    if mode.kind in ("train", "prefill"):
        # prefill attends over the FRESH K/V (window-masked), independent of
        # ring-buffer wrap-around; the cache write keeps only the last W.
        out = blocked_attention(
            q, k, v, positions, positions, window=mode.window,
            block_k=mode.block_k, unroll=unroll,
        )
        new_cache = (
            _write_cache(cache, k, v, positions) if mode.kind == "prefill" else cache
        )
    else:
        new_cache = _write_cache(cache, k, v, positions)
        out = blocked_attention(
            q,
            new_cache["k"],
            new_cache["v"],
            positions,
            new_cache["slot_pos"],
            window=mode.window,
            block_k=mode.block_k,
            unroll=unroll,
        )
    out = out.reshape(B, S, H * hd)
    return jnp.einsum("bse,ed->bsd", out, params["wo"]), new_cache


def _patterned_attention(params, cfg: ModelConfig, q, k, v, positions,
                         mode: AttnMode, kind: str):
    if mode.kind != "train":
        raise NotImplementedError(
            f"{cfg.name}: layers of a `layer_types` pattern run training "
            f"only, not {mode.kind!r}")
    B, S, H, hd = q.shape
    inv_freq, scale = None, 1.0
    if kind == "full" and cfg.yarn_factor:
        inv_freq = yarn_inv_freq(hd, cfg.rope_theta, cfg.yarn_factor,
                                 cfg.yarn_original_max_position,
                                 cfg.yarn_beta_fast, cfg.yarn_beta_slow)
        scale = cfg.yarn_attention_factor
    q = apply_rope(q, positions, cfg.rope_theta, inv_freq, scale)
    k = apply_rope(k, positions, cfg.rope_theta, inv_freq, scale)
    window = cfg.sliding_window if kind == "window" else None
    with jax.named_scope(f"attn.{kind}"):
        out = banded_attention(q, k, v, window, cfg.attn_block,
                               1.0 / hd ** 0.5)
    return jnp.einsum("bse,ed->bsd", out.reshape(B, S, H * hd), params["wo"])


# ===================================================================== MLA
def mla_init(rng, cfg: ModelConfig, dtype):
    d, H = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 6)
    return {
        "wq_a": he_init(ks[0], (d, qr), d, dtype),
        "q_norm": {"scale": jnp.ones((qr,), dtype)},
        "wq_b": he_init(ks[1], (qr, H * (nope + rope)), qr, dtype),
        "wkv_a": he_init(ks[2], (d, kvr + rope), d, dtype),
        "kv_norm": {"scale": jnp.ones((kvr,), dtype)},
        "wk_b": he_init(ks[3], (kvr, H * nope), kvr, dtype),
        "wv_b": he_init(ks[4], (kvr, H * dv), kvr, dtype),
        "wo": he_init(ks[5], (H * dv, d), H * dv, dtype),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    """MLA caches the COMPRESSED latent (kv_lora + rope) — its memory win."""
    return {
        "ckv": jnp.zeros((batch, cache_len, cfg.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, cache_len, cfg.qk_rope_dim), dtype),
        "slot_pos": jnp.full((cache_len,), -1, jnp.int32),
        "pos": jnp.zeros((), jnp.int32),
    }


def _mla_qkv(params, cfg, x, positions):
    from repro.models.layers import rmsnorm

    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope = cfg.qk_nope_dim, cfg.qk_rope_dim
    q_lat = rmsnorm(params["q_norm"], jnp.einsum("bsd,dr->bsr", x, params["wq_a"]))
    q = jnp.einsum("bsr,re->bse", q_lat, params["wq_b"]).reshape(B, S, H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = jnp.einsum("bsd,dr->bsr", x, params["wkv_a"])
    ckv, k_rope = kv_a[..., : cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank :]
    from repro.models.layers import rmsnorm as _rn

    ckv = _rn(params["kv_norm"], ckv)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, k_rope


def mla_apply(params, cfg: ModelConfig, x, positions, cache, mode: AttnMode):
    B, S, _ = x.shape
    H = cfg.num_heads
    nope, rope, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    q_nope, q_rope, ckv, k_rope = _mla_qkv(params, cfg, x, positions)
    scale = 1.0 / ((nope + rope) ** 0.5)

    unroll = not cfg.scan_layers
    if mode.kind in ("train", "prefill"):
        # naive path: expand latent to per-head K/V (linear in S); prefill
        # attends over the FRESH latents and only writes the cache.
        if mode.kind == "prefill":
            cache = _write_mla_cache(cache, ckv, k_rope, positions)
        src_ckv, src_krope, kv_pos = ckv, k_rope, positions
        T = src_ckv.shape[1]
        k_nope = jnp.einsum("btr,re->bte", src_ckv, params["wk_b"]).reshape(
            B, T, H, nope
        )
        val = jnp.einsum("btr,re->bte", src_ckv, params["wv_b"]).reshape(B, T, H, dv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(src_krope[:, :, None, :], (B, T, H, rope))], -1
        )
        q = jnp.concatenate([q_nope, q_rope], -1)
        out = blocked_attention(
            q, k, val, positions, kv_pos, window=mode.window,
            block_k=mode.block_k, scale=scale, unroll=unroll,
        )
    else:
        # absorbed decode: score/combine directly in latent space (MQA-like)
        cache = _write_mla_cache(cache, ckv, k_rope, positions)
        # q' = q_nope @ wk_b^T  (per head): (B,S,H,kvr)
        wk_b = params["wk_b"].reshape(kvr, H, nope)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wk_b)
        q_full = jnp.concatenate([q_lat, q_rope], -1)  # (B,S,H,kvr+rope)
        k_full = jnp.concatenate([cache["ckv"], cache["krope"]], -1)  # (B,T,kvr+rope)
        out_lat = blocked_attention(
            q_full,
            k_full[:, :, None, :],
            cache["ckv"][:, :, None, :],
            positions,
            cache["slot_pos"],
            window=mode.window,
            block_k=mode.block_k,
            scale=scale,
            unroll=unroll,
        )  # (B,S,H,kvr)
        wv_b = params["wv_b"].reshape(kvr, H, dv)
        out = jnp.einsum("bshr,rhv->bshv", out_lat, wv_b)

    out = out.reshape(B, S, H * dv)
    return jnp.einsum("bse,ed->bsd", out, params["wo"]), cache


def _write_mla_cache(cache, ckv, k_rope, positions):
    W = cache["ckv"].shape[1]
    idx = positions % W
    cache = dict(cache)
    cdt = cache["ckv"].dtype  # supports quantized (fp8) caches
    cache["ckv"] = cache["ckv"].at[:, idx].set(ckv.astype(cdt))
    cache["krope"] = cache["krope"].at[:, idx].set(k_rope.astype(cdt))
    cache["slot_pos"] = cache["slot_pos"].at[idx].set(positions)
    cache["pos"] = positions[-1] + 1
    return cache
