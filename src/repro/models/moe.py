"""Mixture-of-Experts layer: top-k routing, capacity-based gather/scatter
dispatch (TPU-classic "dropping" MoE, exact FLOPs accounting), optional
shared experts (deepseek-v3) and dense residual branch (arctic).

Capacity competition is scoped PER SEQUENCE POSITION: the group of B
tokens at position s competes for its own (E, C) slots, which is exactly
the group the serving path routes together at decode step s. That makes
the drop pattern causal — prefill+decode reproduce the train-mode
forward bit-for-bit at the routing level (tests/test_serve.py), where
a flattened (T*k,) group would let batch-0's late tokens steal capacity
from batch-1's early ones.

Dispatch uses gather (`jnp.take`) and scatter-add rather than one-hot
einsums, so HLO FLOPs reflect real expert compute:
  S * E * C * (3 d f) per layer, with C ≈ max(8, capacity_factor * B * k / E)
(the per-group capacity floor makes small-batch dispatch pay for at most
8 slots per expert per position). Expert weights are sharded over the
`model` mesh axis (expert parallelism); GSPMD inserts the token
all-to-all/all-reduce around the sharded expert matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import he_init, silu
from repro.models.mlp import mlp_init, mlp_apply

CAPACITY_FACTOR = 1.25


def moe_init(rng, cfg: ModelConfig, dtype):
    """The router over all `num_experts`, the weights of the experts held
    here (`cfg.local_experts`)."""
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.local_experts
    ks = jax.random.split(rng, 5)
    p = {
        "router": he_init(ks[0], (d, cfg.num_experts), d, jnp.float32),  # fp32
        "experts": {
            "w1": he_init(ks[1], (E, d, f), d, dtype),
            "w3": he_init(ks[2], (E, d, f), d, dtype),
            "w2": he_init(ks[3], (E, f, d), f, dtype),
        },
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_init(ks[4], d, f * cfg.num_shared_experts, dtype)
    return p


def expert_capacity(num_tokens: int, num_experts: int, k: int) -> int:
    cap = int(CAPACITY_FACTOR * num_tokens * k / num_experts)
    return max(8, -(-cap // 8) * 8)  # round up to 8


def moe_apply(params, cfg: ModelConfig, x, *, router_dtype=jnp.float32):
    """x: (B,S,d). Returns (out (B,S,d), aux_loss scalar).

    Routing (top-k, gates, aux loss) is per-token; capacity competition
    is per position group — the B tokens at sequence position s share one
    (E, C) slot budget, matching the decode path's step-s routing group.
    """
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt.astype(router_dtype), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (T,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance auxiliary loss (Switch-style, whole batch) ----
    me = probs.mean(axis=0)  # (E,)
    ce = jnp.zeros((E,), jnp.float32)
    ce = ce.at[expert_idx.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce)

    # ---- capacity-based dispatch, one causal group per position
    # (sort-based positions: O(Bk log Bk) per group, memory O(Bk),
    # instead of the classic (Bk, E) one-hot cumsum) ----
    C = expert_capacity(B, E, k)
    xg = x.transpose(1, 0, 2)  # (S,B,d) — group s = batch column at pos s
    eg = expert_idx.reshape(B, S, k).transpose(1, 0, 2)  # (S,B,k)
    gg = gate_vals.reshape(B, S, k).transpose(1, 0, 2)

    def dispatch(xs, es, gs):
        """One capacity group: xs (B,d), es/gs (B,k)."""
        flat_e = es.reshape(-1)  # (B*k,)
        Bk = flat_e.shape[0]
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(E))  # (E,)
        pos_sorted = jnp.arange(Bk) - starts[sorted_e]
        pos = jnp.zeros((Bk,), jnp.int32).at[order].set(
            pos_sorted.astype(jnp.int32)
        )
        keep = pos < C
        slot = jnp.where(keep, flat_e * C + pos, E * C)  # E*C = drop sentinel
        token_of = jnp.repeat(jnp.arange(B), k)
        buf = jnp.zeros((E * C, d), x.dtype).at[slot].set(
            jnp.take(xs, token_of, axis=0), mode="drop"
        )
        tok_of_slot = jnp.full((E * C,), B, jnp.int32).at[slot].set(
            token_of.astype(jnp.int32), mode="drop"
        )
        gate_of_slot = jnp.zeros((E * C,), jnp.float32).at[slot].set(
            gs.reshape(-1), mode="drop"
        )
        return buf.reshape(E, C, d), tok_of_slot, gate_of_slot

    buf, tok_of_slot, gate_of_slot = jax.vmap(dispatch)(xg, eg, gg)

    # expert FFN (E parallel matmuls per group; E sharded over `model` axis)
    w = params["experts"]
    h = silu(jnp.einsum("secd,edf->secf", buf, w["w1"])) * jnp.einsum(
        "secd,edf->secf", buf, w["w3"]
    )
    out_buf = jnp.einsum("secf,efd->secd", h, w["w2"]).reshape(S, E * C, d)

    # combine in SLOT space: scatter-add expert outputs to their tokens.
    # With out_buf sharded on E (expert parallelism) each shard scatters
    # only its own experts' slots and GSPMD finishes with ONE (S, B, d)
    # all-reduce — a token-indexed gather here would instead all-gather
    # the entire (S, E*C, d) buffer (measured 30x more collective traffic,
    # see EXPERIMENTS.md §Perf H3).
    def combine(ob, tos, gos):
        return jnp.zeros((B, d), jnp.float32).at[tos].add(
            ob.astype(jnp.float32) * gos[:, None], mode="drop"
        )

    combined = jax.vmap(combine)(out_buf, tok_of_slot, gate_of_slot)  # (S,B,d)
    out = combined.transpose(1, 0, 2).astype(x.dtype)

    if "shared" in params:
        out = out + mlp_apply(params["shared"], x)
    return out, aux * cfg.router_aux_coef


def moe_ref_dense(params, cfg: ModelConfig, x):
    """Oracle: every token through its top-k experts via dense per-expert
    masking (exact, no capacity drops). Test-only — O(E * T * d * f)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    xt = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, -1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    w = params["experts"]
    out = jnp.zeros_like(xt)
    for e in range(E):
        h = silu(xt @ w["w1"][e]) * (xt @ w["w3"][e])
        y = h @ w["w2"][e]
        gate_e = ((expert_idx == e) * gate_vals).sum(-1)  # (T,)
        out = out + y * gate_e[:, None].astype(y.dtype)
    res = out.reshape(B, S, d)
    if "shared" in params:
        res = res + mlp_apply(params["shared"], x)
    return res


# ============================================================ expert share
# The layer of one chip under expert parallelism (cfg.experts_held): every
# token is routed over all experts, and the experts held here compute,
# dropping no token, their part of the result. Assignments (token, slot)
# are sorted by held expert, those to experts held elsewhere last; the
# held experts' three products are each ONE grouped product over the
# sorted rows (`jax.lax.ragged_dot`, its own kernel on the TPU) with
# uneven group sizes. The buffer holds every assignment, so no count of
# tokens an expert may take is assumed. Rows past the held groups are
# not computed, and the TPU's ragged dot leaves them unwritten: every
# read of them is a select (on the row, or on `mine`), never a product
# with a zero gate, which would carry a NaN found there.


def _route(params, cfg: ModelConfig, xt):
    """Top-k over all experts, renormalised gates (norm_topk_prob), and
    the assignments sorted by held expert: (order, pos, sizes, weight,
    mine) with order the sorted assignments (a = token * k + slot), pos
    its inverse, sizes the rows of each held expert, weight each
    assignment's gate (0 where its expert is held elsewhere) and mine
    whether its expert is held here (its row lies in a held group)."""
    k, held = cfg.experts_per_token, cfg.local_experts
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    local = idx.reshape(-1) - cfg.first_expert
    mine = (local >= 0) & (local < held)
    key = jnp.where(mine, local, held)  # held elsewhere: one group, last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    pos = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    starts = jnp.searchsorted(key[order], jnp.arange(held + 1))
    sizes = jnp.diff(starts).astype(jnp.int32)
    weight = jnp.where(mine, gate.reshape(-1), 0.0)
    return order, pos, sizes, weight, mine


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(xt, order, pos, mine, k: int):
    """Row r of the sorted buffer is token order[r] // k. Its gradient is
    a gather too (pos is the inverse permutation), not a scatter-add, of
    the rows in held groups only."""
    return jnp.take(xt, order // k, axis=0)


def _dispatch_fwd(xt, order, pos, mine, k):
    return _dispatch(xt, order, pos, mine, k), (order, pos, mine)


def _dispatch_bwd(k, res, d_rows):
    order, pos, mine = res
    d = d_rows.shape[-1]
    picked = jnp.where(mine[:, None], jnp.take(d_rows, pos, axis=0), 0)
    dxt = picked.reshape(-1, k, d)
    return (dxt.astype(jnp.float32).sum(1).astype(d_rows.dtype), None, None,
            None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _held_rows(rows, pos, mine):
    """Assignment a's row, rows[pos[a]], in float32 where its expert is
    held here and 0 where not."""
    picked = jnp.take(rows, pos, axis=0).astype(jnp.float32)
    return jnp.where(mine[:, None], picked, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(rows, weight, order, pos, mine, k: int):
    """out[t] = sum_j weight[t k + j] * rows[pos[t k + j]] over the
    assignments whose expert is held here, in float32."""
    d = rows.shape[-1]
    picked = _held_rows(rows, pos, mine)
    return (picked * weight[:, None]).reshape(-1, k, d).sum(1)


def _combine_fwd(rows, weight, order, pos, mine, k):
    return (_combine(rows, weight, order, pos, mine, k),
            (rows, weight, order, pos, mine))


def _combine_bwd(k, res, dout):
    rows, weight, order, pos, mine = res
    d_tok = jnp.repeat(dout, k, axis=0)  # (T k, d): assignment a's token
    d_rows = jnp.take(d_tok * weight[:, None], order, axis=0)
    d_weight = jnp.sum(_held_rows(rows, pos, mine) * d_tok, axis=-1)
    return d_rows.astype(rows.dtype), d_weight, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _ffn(rows, sizes, w1, w3, w2):
    # each product is 0 past the held groups, so that neither what the
    # kernel leaves there nor its gradient enters another product
    held = jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes)
    mm = lambda a, w: jnp.where(held, jax.lax.ragged_dot(a, w, sizes), 0)
    h = silu(mm(rows, w1)) * mm(rows, w3)
    return mm(h, w2)


def _ffn_grads(rows, sizes, w1, w3, w2, dy):
    _, vjp = jax.vjp(lambda r, a, b, c: _ffn(r, sizes, a, b, c),
                     rows, w1, w3, w2)
    return vjp(dy)


# ragged_dot has no batching rule over a client axis: under the engine's
# per-client vmap each client's grouped products run in turn
_ffn_each = jax.custom_batching.sequential_vmap(_ffn)
_ffn_grads_each = jax.custom_batching.sequential_vmap(_ffn_grads)


@jax.custom_vjp
def grouped_ffn(rows, sizes, w1, w3, w2):
    """The held experts' SwiGLU over rows sorted by expert: sizes[e] rows
    for held expert e, in order, and zeros past them."""
    return _ffn_each(rows, sizes, w1, w3, w2)


def _grouped_ffn_fwd(rows, sizes, w1, w3, w2):
    return _ffn_each(rows, sizes, w1, w3, w2), (rows, sizes, w1, w3, w2)


def _grouped_ffn_bwd(res, dy):
    rows, sizes, w1, w3, w2 = res
    d_rows, dw1, dw3, dw2 = _ffn_grads_each(rows, sizes, w1, w3, w2, dy)
    return d_rows, None, dw1, dw3, dw2


grouped_ffn.defvjp(_grouped_ffn_fwd, _grouped_ffn_bwd)


def moe_share_apply(params, cfg: ModelConfig, x):
    """x: (B,S,d). The part of the layer's output that the experts held
    here give (out (B,S,d), aux 0): the router runs at its full width,
    and no token routed to a held expert is dropped. The sum of these
    parts over shares that hold every expert once is the whole layer."""
    B, S, d = x.shape
    k = cfg.experts_per_token
    xt = x.reshape(B * S, d)
    with jax.named_scope("moe.route"):
        order, pos, sizes, weight, mine = _route(params, cfg, xt)
    with jax.named_scope("moe.experts"):
        w = params["experts"]
        rows = _dispatch(xt, order, pos, mine, k)
        rows = grouped_ffn(rows, sizes, w["w1"], w["w3"], w["w2"])
        out = _combine(rows, weight, order, pos, mine, k)
    return out.reshape(B, S, d).astype(x.dtype), jnp.zeros((), jnp.float32)


def moe_share_ref_dense(params, cfg: ModelConfig, x):
    """Oracle of `moe_share_apply`: every token through each held expert,
    masked by its gate (0 unless routed there). Test-only."""
    B, S, d = x.shape
    k, held, e0 = cfg.experts_per_token, cfg.local_experts, cfg.first_expert
    xt = x.reshape(-1, d).astype(jnp.float32)
    logits = xt @ params["router"].astype(jnp.float32)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), params["experts"])
    out = jnp.zeros_like(xt)
    for e in range(held):
        y = (silu(xt @ w["w1"][e]) * (xt @ w["w3"][e])) @ w["w2"][e]
        out = out + y * ((idx == e0 + e) * gate).sum(-1)[:, None]
    return out.reshape(B, S, d)
