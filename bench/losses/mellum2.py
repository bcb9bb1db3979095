"""Mellum2-12B-A2.5B's language-model loss, in plain terms for the
reference: per silo, the mean next-token cross-entropy over its token
sequences, and its gradient in the flat parameters.

Written from the published configuration (the keys of the catalog's
`config.json` at the top of the configuration file) and imports nothing
of `repro`. Float32 `jnp`, every product at `Precision.HIGHEST`, no cache
and no kernel; `jax.grad` of the forward pass gives the gradient. Per
layer, with RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w:

  h = x + Attn(RMSNorm(x)),    x' = h + MoE(RMSNorm(h))

- Attn: GQA, `num_attention_heads` queries over `num_key_value_heads`
  keys and values of `head_dim`, no bias, no qk-norm; softmax of
  q.k / sqrt(head_dim). Layer i's kind is `layer_types[i]`: a
  "sliding_attention" layer sees the keys with 0 <= q - k <
  `sliding_window` and rotates q and k by plain RoPE at its rope_theta;
  a "full_attention" layer sees every k <= q and uses YaRN: inverse
  frequencies blended between θ^(-2i/d) and that over `factor` by a
  linear ramp between the correction dimensions of beta_fast and
  beta_slow turns over `original_max_position_embeddings` (rounded
  outward), and cos and sin times `attention_factor`. Rotation pairs
  dimension i with i + head_dim/2. Computed in blocks of queries, each
  against the keys it may see, so that it fits.
- MoE: router (hidden, `router_experts`) with softmax over all experts,
  the top `num_experts_per_tok`, gates renormalised
  (`norm_topk_prob`); expert e gives (silu(x W1) * (x W3)) W2 of width
  `moe_intermediate_size`. The chip holds `num_experts` experts from id
  `first_expert`: each is computed for every token, masked by the
  token's gate (0 unless routed there). What experts held elsewhere
  add is left out, as in the program.
- Output: RMSNorm, then the untied head over `vocab_size` ids; labels
  are the inputs shifted by one.

Departures from the published model: the cut (depth, experts held,
vocabulary slice; the configuration's `cut`); no load-balance term
(fine-tuning); no MTP head (none in the config).

Parameters are flat in the order of the program's pytree leaves (keys
sorted at every level), each leaf raveled:
embed (V, d); final_norm (d,); per layer, stacked on a leading axis of
the layers: attn wk, wo, wq, wv; experts w1, w2, w3; router; norm1,
norm2; lm_head (d, V).

`precision="high"` is the control, one step below the configuration's
stated precision (bfloat16 operands at every product): every product's
two operands rounded to 3 bits of mantissa, float8 e4m3's, at
bfloat16's exponent range so that no scaling enters; products
accumulate in float32 and everything else stays float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import counting

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 128  # queries a block of the attention
CE_BLOCK = 2048  # tokens a block of the loss


def dims(cfg: dict) -> dict:
    layers = cfg["num_hidden_layers"]
    return {
        "d": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "Kv": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "f": cfg["moe_intermediate_size"], "E": cfg["router_experts"],
        "Eh": cfg["num_experts"], "e0": cfg["first_expert"],
        "k": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
        "L": layers, "kinds": cfg["layer_types"][:layers],
    }


def leaves(cfg: dict) -> list:
    """(name, shape, fan_in) of each parameter leaf, in flat order; fan_in
    None for a norm's scale (ones), 0 for the embedding (std 0.02)."""
    g = dims(cfg)
    d, H, Kv, hd, f, L = g["d"], g["H"], g["Kv"], g["hd"], g["f"], g["L"]
    return [
        ("embed", (g["V"], d), 0),
        ("final_norm", (d,), None),
        ("wk", (L, d, Kv * hd), d),
        ("wo", (L, H * hd, d), H * hd),
        ("wq", (L, d, H * hd), d),
        ("wv", (L, d, Kv * hd), d),
        ("w1", (L, g["Eh"], d, f), d),
        ("w2", (L, g["Eh"], f, d), f),
        ("w3", (L, g["Eh"], d, f), d),
        ("router", (L, d, g["E"]), d),
        ("norm1", (L, d), None),
        ("norm2", (L, d), None),
        ("lm_head", (d, g["V"]), d),
    ]


def size(cfg: dict) -> int:
    return sum(int(np.prod(s)) for _, s, _ in leaves(cfg))


PER_LAYER = ("wk", "wo", "wq", "wv", "w1", "w2", "w3", "router", "norm1",
             "norm2")


def layout(cfg: dict) -> list:
    """(name, layer or None, shape) of each piece of x, in flat order: the
    leaves, and a leaf stacked over the layers cut into its layers."""
    out = []
    for name, shape, _ in leaves(cfg):
        if name in PER_LAYER:
            out += [(name, i, shape[1:]) for i in range(shape[0])]
        else:
            out.append((name, None, shape))
    return out


def pieces(cfg: dict, x) -> list:
    """x cut into the arrays of its `layout`. The gradient is taken in
    these pieces and joined once (`loss_grad`), so that no layer's
    gradient is laid into a whole leaf of zeros. Each piece is cut from
    x before it is shaped: XLA would otherwise shape the whole of x
    once for each width of row it is cut into, a copy of x each."""
    out, at = [], 0
    for _, _, shape in layout(cfg):
        n = int(np.prod(shape))
        out.append(jax.lax.optimization_barrier(x[at:at + n]).reshape(shape))
        at += n
    return out


def init(cfg: dict, seed: int) -> np.ndarray:
    """x⁰: weights normal over sqrt(fan-in), the embedding normal at 0.02,
    norms 1; every value a bfloat16 one, so that the program's bfloat16
    parameters start where the reference does."""
    rng = np.random.default_rng(seed)
    parts = []
    for _, shape, fan_in in leaves(cfg):
        if fan_in is None:
            parts.append(np.ones(int(np.prod(shape)), np.float32))
            continue
        std = 0.02 if fan_in == 0 else 1.0 / math.sqrt(fan_in)
        parts.append(rng.standard_normal(int(np.prod(shape)), np.float32)
                     * np.float32(std))
    x = np.concatenate(parts)
    return x.astype(jnp.bfloat16).astype(np.float32)


# ------------------------------------------------------------ the model
def _operand(a, precision: str):
    if precision == "highest":
        return a
    return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3)


def _mm(subscripts: str, a, b, precision: str):
    return jnp.einsum(subscripts, _operand(a, precision),
                      _operand(b, precision), precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _inv_freq(cfg: dict, kind: str):
    p = cfg["rope_parameters"][kind]
    hd, theta = cfg["head_dim"], float(p["rope_theta"])
    base = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if p["rope_type"] == "default":
        return base, 1.0
    orig = p["original_max_position_embeddings"]

    def correction_dim(turns):
        return hd * math.log(orig / (turns * 2 * math.pi)) / \
            (2 * math.log(theta))

    low = max(math.floor(correction_dim(p["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(p["beta_slow"])), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = base / p["factor"] * ramp + base * (1 - ramp)
    return inv, p["attention_factor"]


def _rope(x, inv_freq, scale):
    """x (B, S, heads, hd), positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    ang = np.arange(S)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(scale * np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(scale * np.sin(ang), jnp.float32)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, precision):
    """q (B,S,H,hd), k and v (B,S,H,hd) (heads already repeated); each
    block of Q_BLOCK queries against the keys it may see: all earlier
    ones, or for a window the window and the block's own span."""
    B, S, H, hd = q.shape
    nb = min(Q_BLOCK, S)
    assert S % nb == 0, (S, nb)
    span = S if window is None else min(S, window + nb)

    @jax.checkpoint
    def block(i):
        q0 = i * nb
        k0 = 0 if window is None else jnp.clip(q0 + nb - span, 0, S - span)
        qi = jax.lax.dynamic_slice_in_dim(q, q0, nb, 1)
        ki = jax.lax.dynamic_slice_in_dim(k, k0, span, 1)
        vi = jax.lax.dynamic_slice_in_dim(v, k0, span, 1)
        s = _mm("bqhd,bkhd->bhqk", qi, ki, precision) / math.sqrt(hd)
        lag = (q0 + jnp.arange(nb))[:, None] - \
            (k0 + jnp.arange(span))[None, :]
        ok = lag >= 0
        if window is not None:
            ok &= lag < window
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return _mm("bhqk,bkhd->bqhd", p, vi, precision)

    out = jax.lax.map(block, jnp.arange(S // nb))  # (S/nb, B, nb, H, hd)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H * hd)


def _layer(cfg, g, p, x, kind, precision):
    eps = cfg["rms_norm_eps"]
    B, S, d = x.shape
    H, Kv, hd = g["H"], g["Kv"], g["hd"]
    xn = _rmsnorm(x, p["norm1"], eps)
    q = _mm("bsd,de->bse", xn, p["wq"], precision).reshape(B, S, H, hd)
    k = _mm("bsd,de->bse", xn, p["wk"], precision).reshape(B, S, Kv, hd)
    v = _mm("bsd,de->bse", xn, p["wv"], precision).reshape(B, S, Kv, hd)
    inv, scale = _inv_freq(cfg, kind)
    q, k = _rope(q, inv, scale), _rope(k, inv, scale)
    k, v = (jnp.repeat(a, H // Kv, axis=2) for a in (k, v))
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    o = _attention(q, k, v, window, precision)
    h = x + _mm("bse,ed->bsd", o, p["wo"], precision)

    hn = _rmsnorm(h, p["norm2"], eps).reshape(B * S, d)
    probs = jax.nn.softmax(_mm("td,de->te", hn, p["router"], precision), -1)
    gate, idx = jax.lax.top_k(probs, g["k"])
    gate = gate / gate.sum(-1, keepdims=True)
    moe = jnp.zeros_like(hn)
    for e in range(g["Eh"]):
        a = _mm("td,df->tf", hn, p["w1"][e], precision)
        b = _mm("td,df->tf", hn, p["w3"][e], precision)
        y = _mm("tf,fd->td", jax.nn.silu(a) * b, p["w2"][e], precision)
        moe = moe + y * jnp.sum(jnp.where(idx == g["e0"] + e, gate, 0.0),
                                -1, keepdims=True)
    return h + moe.reshape(B, S, d)


def loss(cfg: dict, arrays: list, tokens, precision: str = "highest"):
    """Mean next-token cross-entropy of one silo's sequences (B, S + 1)
    at the flat parameters x, given as the arrays of its `pieces`."""
    g = dims(cfg)
    p, layers = {}, [{} for _ in g["kinds"]]
    for (name, i, _), a in zip(layout(cfg), arrays):
        if i is None:
            p[name] = a
        else:
            layers[i][name] = a
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    h = jnp.take(p["embed"], inputs, axis=0)
    for pi, kind in zip(layers, g["kinds"]):
        h = jax.checkpoint(
            lambda h, pi, kind=kind: _layer(cfg, g, pi, h, kind, precision)
        )(h, pi)
    hn = _rmsnorm(h, p["final_norm"], cfg["rms_norm_eps"])
    T = labels.size
    chunk = min(CE_BLOCK, T)

    @jax.checkpoint
    def nll(args):  # summed over a block of tokens
        hc, yc = args
        logits = _mm("td,dv->tv", hc, p["lm_head"], precision)
        return jnp.sum(jax.nn.logsumexp(logits, -1)
                       - jnp.take_along_axis(logits, yc[:, None], -1)[:, 0])

    blocks = (hn.reshape(T // chunk, chunk, -1),
              labels.reshape(T // chunk, chunk))
    return jnp.sum(jax.lax.map(nll, blocks)) / T


def _mesh(cfg: dict):
    """The cell's `data` mesh, made as the harness makes it
    (bench/workload.py); None on one chip."""
    chips = cfg.get("chips", 1)
    if chips <= 1:
        return None
    from jax.sharding import AxisType

    return jax.make_mesh((chips, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:chips])


def loss_grad(cfg: dict, data: dict, x, precision: str):
    """Per-silo f_i(x) (m,) and ∇f_i(x) (m, n). Over the cell's mesh each
    chip computes the silos whose rows it holds, at the whole x."""
    def one(x, tokens):
        f, grads = jax.value_and_grad(
            lambda arrays: loss(cfg, arrays, tokens, precision))(
                pieces(cfg, x))
        return f, jnp.concatenate([gr.reshape(-1) for gr in grads])

    per_silo = jax.vmap(one, in_axes=(None, 0))
    mesh = _mesh(cfg)
    if mesh is None:
        return per_silo(x, data["tokens"])
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(per_silo, mesh=mesh, in_specs=(P(), P("data")),
                         out_specs=(P("data"), P("data")),
                         check_vma=False)(x, data["tokens"])


# ------------------------------------------------------------ the counts
def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations of one token's forward pass, averaged over a sequence
    of seq_len: the products at their widths, attention over the keys a
    query sees (a sliding layer's band, S·W less the start, not S²), and
    the held experts at their expected load under uniform routing
    (k of E experts a token, so num_experts / E of the assignments)."""
    g = dims(cfg)
    d, H, Kv, hd, S, W = g["d"], g["H"], g["Kv"], g["hd"], seq_len, \
        cfg["sliding_window"]
    proj = 2 * d * (2 * H * hd + 2 * Kv * hd)
    keys = {"full_attention": (S + 1) / 2,
            "sliding_attention": (W * (W + 1) / 2 + (S - W) * W) / S}
    experts = g["k"] * g["Eh"] / g["E"] * 3 * 2 * d * g["f"]
    per_layer = [proj + 4 * H * hd * keys[kind] + 2 * d * g["E"] + experts
                 for kind in g["kinds"]]
    return sum(per_layer) + 2 * d * g["V"]


def counts(cfg: dict) -> dict:
    """{"flops_per_round", "kernel_bytes_per_round"}: forward and backward
    (3× the forward's products, no recomputation) over every silo's
    tokens, FedGiA's elementwise work per parameter and client, and the
    fedgia_update kernel's bytes (bench/counting.py)."""
    m, n = cfg["num_clients"], cfg["dim"]
    tokens = m * cfg["sequences"] * cfg["seq_len"]
    model = 3 * tokens * forward_flops_per_token(cfg, cfg["seq_len"])
    fedgia = m * n * (counting.kernel_elementwise_ops(cfg["k0"])
                      + counting.ROUND_ELEMENTWISE_OPS)
    return {"flops_per_round": float(model + fedgia),
            "kernel_bytes_per_round": float(counting.kernel_bytes(m, n))}
