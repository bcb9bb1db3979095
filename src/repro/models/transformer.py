"""Decoder-only transformer assembling all assigned architecture families.

Layers are stacked into homogeneous *groups* (deepseek-v3: leading dense
layers + MoE layers = two groups) and executed with `jax.lax.scan` over the
stacked parameters — small HLO, fast compiles at 95 layers, remat-friendly.

Modes:
  train    — full causal attention, no cache, returns loss-ready logits
  prefill  — causal attention, writes the KV cache, returns logits
  decode   — ONE new token against a seq_len cache (ring buffer when the
             sliding-window long-context variant is on)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import rwkv as rwkv_lib
from repro.models import ssm as ssm_lib
from repro.models.attention import AttnMode
from repro.models.layers import embed_init, he_init, rmsnorm, rmsnorm_init
from repro.models.mlp import mlp_apply, mlp_init

IGNORE_LABEL = -1
MTP_WEIGHT = 0.3


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    name: str
    count: int
    kind: str  # dense | moe | rwkv | hybrid


def _layer_groups(cfg: ModelConfig):
    if cfg.attention_type == "rwkv":
        return [LayerGroup("rwkv", cfg.num_layers, "rwkv")]
    if cfg.attention_type == "hybrid":
        return [LayerGroup("hybrid", cfg.num_layers, "hybrid")]
    if cfg.moe:
        groups = []
        if cfg.first_dense_layers:
            groups.append(LayerGroup("dense", cfg.first_dense_layers, "dense"))
        groups.append(
            LayerGroup("moe", cfg.num_layers - cfg.first_dense_layers, "moe")
        )
        return groups
    return [LayerGroup("dense", cfg.num_layers, "dense")]


class Transformer:
    """Functional model: params are plain dict pytrees."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.groups = _layer_groups(cfg)
        self.dtype = jnp.dtype(cfg.dtype)

    # ------------------------------------------------------------------ init
    def _attn_init(self, rng):
        if self.cfg.attention_type == "mla":
            return attn_lib.mla_init(rng, self.cfg, self.dtype)
        return attn_lib.gqa_init(rng, self.cfg, self.dtype)

    def _block_init(self, kind: str, rng):
        cfg, dt = self.cfg, self.dtype
        d = cfg.d_model
        ks = jax.random.split(rng, 6)
        if kind == "rwkv":
            return {
                "norm1": rmsnorm_init(d, dt),
                "time_mix": rwkv_lib.time_mix_init(ks[0], cfg, dt),
                "norm2": rmsnorm_init(d, dt),
                "channel_mix": rwkv_lib.channel_mix_init(ks[1], cfg, dt),
            }
        p = {
            "norm1": rmsnorm_init(d, dt),
            "attn": self._attn_init(ks[0]),
            "norm2": rmsnorm_init(d, dt),
        }
        if kind == "hybrid":
            p["ssm"] = ssm_lib.ssm_init(ks[1], cfg, dt)
            p["mix_attn"] = jnp.ones((d,), dt) * 0.5
            p["mix_ssm"] = jnp.ones((d,), dt) * 0.5
            p["mlp"] = mlp_init(ks[2], d, cfg.d_ff, dt)
        elif kind == "moe":
            p["moe"] = moe_lib.moe_init(ks[1], cfg, dt)
            if cfg.dense_residual:
                p["mlp"] = mlp_init(ks[2], d, cfg.d_ff, dt)
        else:
            p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, dt)
        return p

    def init(self, rng) -> Dict[str, Any]:
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(rng, len(self.groups) + 4)
        params: Dict[str, Any] = {
            "embed": embed_init(ks[0], (cfg.vocab_size, cfg.d_model), dt),
            "final_norm": rmsnorm_init(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = he_init(
                ks[1], (cfg.d_model, cfg.vocab_size), cfg.d_model, dt
            )
        params["groups"] = {}
        for g, k in zip(self.groups, ks[2:]):
            layer_keys = jax.random.split(k, g.count)
            params["groups"][g.name] = jax.vmap(
                functools.partial(self._block_init, g.kind)
            )(layer_keys)
        if cfg.mtp:
            k_mtp = ks[len(self.groups) + 2]
            km = jax.random.split(k_mtp, 2)
            params["mtp"] = {
                "proj": he_init(km[0], (2 * cfg.d_model, cfg.d_model), 2 * cfg.d_model, dt),
                "block": self._block_init("dense", km[1]),
                "norm": rmsnorm_init(cfg.d_model, dt),
            }
        return params

    # ----------------------------------------------------------------- cache
    def _block_cache(self, kind: str, batch: int, cache_len: int, dtype):
        cfg = self.cfg
        if kind == "rwkv":
            return rwkv_lib.init_rwkv_state(cfg, batch, dtype)
        if cfg.attention_type == "mla":
            c = attn_lib.init_mla_cache(cfg, batch, cache_len, dtype)
        else:
            c = attn_lib.init_gqa_cache(cfg, batch, cache_len, dtype)
        if kind == "hybrid":
            c = {"attn": c, "ssm_state": ssm_lib.init_ssm_state(cfg, batch)}
        return c

    def init_cache(self, batch: int, cache_len: int, dtype=None):
        dtype = dtype or self.dtype
        out = {}
        for g in self.groups:
            single = self._block_cache(g.kind, batch, cache_len, dtype)
            out[g.name] = jax.tree.map(
                lambda a: jnp.tile(a[None], (g.count,) + (1,) * a.ndim), single
            )
        return out

    # ----------------------------------------------------------------- apply
    def _block_apply(self, kind: str, params, x, cache, positions, mode: AttnMode,
                     attn_kind: Optional[str] = None):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        if kind == "rwkv":
            tm_state = (
                cache
                if cache
                else rwkv_lib.init_rwkv_state(cfg, x.shape[0], x.dtype)
            )
            h, tm_new = rwkv_lib.time_mix_apply(
                params["time_mix"], cfg, rmsnorm(params["norm1"], x, cfg.norm_eps),
                {"shift": tm_state["shift"], "wkv": tm_state["wkv"]},
            )
            x = x + h
            h, cm_new = rwkv_lib.channel_mix_apply(
                params["channel_mix"], rmsnorm(params["norm2"], x, cfg.norm_eps),
                tm_state["cm_shift"],
            )
            x = x + h
            new_cache = (
                {"shift": tm_new["shift"], "wkv": tm_new["wkv"], "cm_shift": cm_new}
                if cache
                else {}
            )
            return x, new_cache, aux

        attn_cache = cache.get("attn", cache) if cache else None
        xn = rmsnorm(params["norm1"], x, cfg.norm_eps)
        if cfg.attention_type == "mla":
            h, attn_cache_new = attn_lib.mla_apply(
                params["attn"], cfg, xn, positions, attn_cache, mode
            )
        else:
            h, attn_cache_new = attn_lib.gqa_apply(
                params["attn"], cfg, xn, positions, attn_cache, mode,
                kind=attn_kind,
            )
        if kind == "hybrid":
            ssm_state = (
                cache["ssm_state"]
                if cache
                else ssm_lib.init_ssm_state(cfg, x.shape[0])
            )
            h_ssm, ssm_new = ssm_lib.ssm_apply(params["ssm"], cfg, xn, ssm_state)
            h = params["mix_attn"] * h + params["mix_ssm"] * h_ssm
        x = x + h

        xn = rmsnorm(params["norm2"], x, cfg.norm_eps)
        if kind == "moe":
            moe_apply = (moe_lib.moe_share_apply if cfg.experts_held
                         else moe_lib.moe_apply)
            h, aux = moe_apply(params["moe"], cfg, xn)
            if cfg.dense_residual:
                h = h + mlp_apply(params["mlp"], xn)
        else:
            h = mlp_apply(params["mlp"], xn)
        x = x + h

        if not cache:
            new_cache = {}
        elif kind == "hybrid":
            new_cache = {"attn": attn_cache_new, "ssm_state": ssm_new}
        else:
            new_cache = attn_cache_new
        return x, new_cache, aux

    def _run_periods(self, group: LayerGroup, params, x, positions, mode):
        """`cfg.layer_types`: the group's layers as periods of that
        pattern, one scan step a period, each layer its own remat."""
        cfg = self.cfg
        P = len(cfg.layer_types)

        def period(carry, p):
            x, aux = carry
            for i, attn_kind in enumerate(cfg.layer_types):
                def layer(x, p_i, attn_kind=attn_kind):
                    x, _, a = self._block_apply(group.kind, p_i, x, {},
                                                positions, mode, attn_kind)
                    return x, a
                if cfg.remat and mode.kind == "train":
                    layer = jax.checkpoint(layer)
                x, a = layer(x, jax.tree.map(lambda l: l[i], p))
                aux = aux + a
            return (x, aux), None

        stacked = jax.tree.map(
            lambda l: l.reshape((group.count // P, P) + l.shape[1:]), params)
        carry = (x, jnp.zeros((), jnp.float32))
        if cfg.scan_layers:
            carry, _ = jax.lax.scan(period, carry, stacked)
        else:
            for n in range(group.count // P):
                carry, _ = period(carry, jax.tree.map(lambda l: l[n], stacked))
        return carry[0], {}, carry[1]

    def _run_group(self, group: LayerGroup, params, x, cache, positions, mode):
        if self.cfg.layer_types:
            return self._run_periods(group, params, x, positions, mode)
        if not self.cfg.scan_layers:
            # straight-line layers (dry-run cost pass: scan bodies are
            # counted once by XLA cost_analysis, so unroll for accounting)
            aux = jnp.zeros((), jnp.float32)
            new_caches = []
            for i in range(group.count):
                p_i = jax.tree.map(lambda a: a[i], params)
                c_i = jax.tree.map(lambda a: a[i], cache) if cache else {}
                x, c_new, a = self._block_apply(
                    group.kind, p_i, x, c_i, positions, mode
                )
                aux += a
                new_caches.append(c_new)
            if cache:
                new_cache = jax.tree.map(
                    lambda *xs: jnp.stack(xs, axis=0), *new_caches
                )
            else:
                new_cache = {}
            return x, new_cache, aux

        def body(carry, xs):
            x, aux = carry
            p, c = xs
            x, c_new, a = self._block_apply(group.kind, p, x, c, positions, mode)
            return (x, aux + a), c_new

        if self.cfg.remat and mode.kind == "train":
            body = jax.checkpoint(body)
        (x, aux), new_cache = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (params, cache if cache else {})
        )
        return x, new_cache, aux

    def forward(
        self,
        params,
        *,
        tokens: Optional[jax.Array] = None,
        embeds: Optional[jax.Array] = None,
        cache=None,
        positions: Optional[jax.Array] = None,
        mode: AttnMode = AttnMode("train"),
    ):
        """Returns (logits, new_cache, aux_dict). positions: (S,) int32."""
        cfg = self.cfg
        parts = []
        if embeds is not None:
            parts.append(embeds.astype(self.dtype))
        if tokens is not None:
            parts.append(jnp.take(params["embed"], tokens, axis=0))
        x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
        S = x.shape[1]
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)

        new_cache = {}
        aux_total = jnp.zeros((), jnp.float32)
        hidden_pre_final = None
        for g in self.groups:
            c = cache[g.name] if cache else None
            x, c_new, aux = self._run_group(
                g, params["groups"][g.name], x, c, positions, mode
            )
            new_cache[g.name] = c_new
            aux_total += aux
        hidden_pre_final = x
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        head = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        )
        logits = jnp.einsum("bsd,dv->bsv", x, head)
        return logits, (new_cache if cache else None), {
            "moe_aux": aux_total,
            "hidden": hidden_pre_final,
        }

    # ------------------------------------------------------------------ loss
    def loss(self, params, batch, mode: AttnMode = AttnMode("train")):
        """batch: {"tokens": (B,S+1)} | {"embeds": (B,S,d), "labels": (B,S)}
        | {"embeds": (B,P,d), "tokens": (B,St+1)} (vlm).
        Returns (loss, metrics)."""
        cfg = self.cfg
        embeds = batch.get("embeds")
        tokens = batch.get("tokens")
        if tokens is not None:
            inputs, tok_labels = tokens[:, :-1], tokens[:, 1:]
        else:
            inputs, tok_labels = None, batch["labels"]
        logits, _, aux = self.forward(
            params, tokens=inputs, embeds=embeds, mode=mode
        )
        if embeds is not None and tokens is not None:
            # vlm: no loss on the image-embedding prefix
            P = embeds.shape[1]
            prefix = jnp.full((tok_labels.shape[0], P), IGNORE_LABEL, tok_labels.dtype)
            labels = jnp.concatenate([prefix, tok_labels], axis=1)
        else:
            labels = tok_labels
        ce, acc = _masked_ce(logits, labels)
        total = ce + aux["moe_aux"]
        metrics = {"ce": ce, "moe_aux": aux["moe_aux"], "acc": acc}
        if cfg.mtp and tokens is not None:
            mtp_loss = self._mtp_loss(params, aux["hidden"], inputs, labels)
            total = total + MTP_WEIGHT * mtp_loss
            metrics["mtp"] = mtp_loss
        metrics["loss"] = total
        return total, metrics

    def _mtp_loss(self, params, hidden, inputs, labels):
        """DeepSeek-V3 multi-token prediction: predict t+2 from [h_t; emb_{t+1}]."""
        cfg = self.cfg
        emb_next = jnp.take(params["embed"], inputs, axis=0)  # embeds of token t
        # shift: combine h_{t} with emb of token t+1 (= inputs shifted left)
        h = hidden[:, :-1]
        e = emb_next[:, 1:]
        z = jnp.concatenate([h, e], axis=-1)
        z = jnp.einsum("bsd,de->bse", z, params["mtp"]["proj"])
        S = z.shape[1]
        pos = jnp.arange(S, dtype=jnp.int32)
        z, _, _ = self._block_apply(
            "dense", params["mtp"]["block"], z, None, pos, AttnMode("train")
        )
        z = rmsnorm(params["mtp"]["norm"], z, cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", z, head)
        mtp_labels = labels[:, 1:]  # predict one further ahead
        ce, _ = _masked_ce(logits, mtp_labels)
        return ce

    # ------------------------------------------------------------- serving
    def prefill(self, params, *, tokens=None, embeds=None, cache_len: int,
                window: Optional[int] = None, cache_dtype=None):
        B = (tokens if tokens is not None else embeds).shape[0]
        cache = self.init_cache(B, cache_len, cache_dtype)
        mode = AttnMode("prefill", window=window)
        logits, cache, _ = self.forward(
            params, tokens=tokens, embeds=embeds, cache=cache, mode=mode
        )
        return logits[:, -1], cache

    def decode_step(self, params, cache, tokens, pos, window: Optional[int] = None):
        """tokens: (B,1) int32; pos: () int32 absolute position."""
        positions = pos[None].astype(jnp.int32)
        mode = AttnMode("decode", window=window)
        logits, cache, _ = self.forward(
            params, tokens=tokens, cache=cache, positions=positions, mode=mode
        )
        return logits[:, -1], cache


def _masked_ce(logits, labels):
    mask = labels != IGNORE_LABEL
    safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1)
    ce = -(ll * mask).sum() / denom
    acc = ((logits.argmax(-1) == safe) * mask).sum() / denom
    return ce, acc
