"""Mellum2-12B-A2.5B's mechanisms against plain forms, on the CPU:
YaRN's frequencies, the banded attention of window and full layers, the
dropless expert share, the scanned layer period, and `run_rounds`
taking a caller's state over (`consume`)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import Transformer
from repro.models import moe as moe_lib
from repro.models.attention import banded_attention, blocked_attention
from repro.models.layers import yarn_inv_freq

MELLUM = get_config("mellum2-12b-a2.5b")


def test_yarn_frequencies_follow_the_published_formula():
    """Hugging Face's `_compute_yarn_parameters` at the config's constants
    (head_dim 128, θ 500,000, factor 16 over 8,192, β 32 and 1), written
    out here in float64: correction dimensions 18.08 -> 18 and 34.98 ->
    35, a linear ramp between them."""
    hd, theta, factor, orig = 128, 500_000.0, 16.0, 8192
    dim = lambda turns: hd * math.log(orig / (turns * 2 * math.pi)) / (
        2 * math.log(theta))
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35)
    base = 1.0 / theta ** (np.arange(0, hd, 2) / hd)
    extrapolate = 1.0 - np.clip((np.arange(hd // 2) - 18) / (35 - 18), 0, 1)
    want = base / factor * (1 - extrapolate) + base * extrapolate
    got = yarn_inv_freq(hd, MELLUM.rope_theta, MELLUM.yarn_factor,
                        MELLUM.yarn_original_max_position,
                        MELLUM.yarn_beta_fast, MELLUM.yarn_beta_slow)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    # the fastest dimensions keep their frequency, the slowest are divided
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(
        base[-1] / factor, rel=1e-6)


@pytest.mark.parametrize("window,block", [(None, 8), (None, 16), (5, 4),
                                          (8, 8), (13, 8), (100, 16)])
def test_banded_attention_is_masked_full_attention(window, block):
    """Values and gradients equal the streaming softmax over every key
    with the mask applied, at S = 37, no whole number of blocks; float32,
    so the two agree to sums taken in another order."""
    B, S, H, Kv, D = 2, 37, 4, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Kv, D))
    v = jax.random.normal(ks[2], (B, S, Kv, D))
    pos = jnp.arange(S)
    scale = 1.0 / math.sqrt(D)
    band = lambda q, k, v: banded_attention(q, k, v, window, block, scale)
    full = lambda q, k, v: blocked_attention(q, k, v, pos, pos,
                                             window=window, block_k=S)
    np.testing.assert_allclose(band(q, k, v), full(q, k, v), atol=2e-6)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    got = jax.grad(loss(band), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(full), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


def _moe_cfg(**kw):
    kw = dict(dict(experts_held=8), **kw)
    return dataclasses.replace(MELLUM, d_model=32, num_experts=8,
                               experts_per_token=3, moe_d_ff=16,
                               dtype="float32", **kw)


def test_expert_shares_add_up_to_the_whole_layer():
    """Four shares of 2 experts each, summed, give the uncut layer's
    dense masked output: every routed (token, expert) pair counted once."""
    cfg = _moe_cfg()
    params = moe_lib.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))
    total = 0.0
    for first in range(0, 8, 2):
        share = dataclasses.replace(cfg, experts_held=2, first_expert=first)
        held = dict(params, experts=jax.tree.map(
            lambda w: w[first:first + 2], params["experts"]))
        total = total + moe_lib.moe_share_apply(held, share, x)[0]
    np.testing.assert_allclose(total, moe_lib.moe_share_ref_dense(params, cfg, x),
                               atol=1e-6)


def test_an_expert_share_is_its_dense_masked_part_under_vmap():
    """One share (experts 4 and 5) against its oracle, in values and in
    gradients taken per client under vmap, as the engine takes them."""
    cfg = _moe_cfg(experts_held=2, first_expert=4)
    params = moe_lib.moe_init(jax.random.PRNGKey(2), cfg, jnp.float32)
    xs = jax.random.normal(jax.random.PRNGKey(3), (3, 2, 7, 32))
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, cfg, x)))
    share = lambda p, c, x: moe_lib.moe_share_apply(p, c, x)[0]
    got = jax.vmap(jax.grad(loss(share), argnums=(0, 1)),
                   in_axes=(None, 0))(params, xs)
    want = jax.vmap(jax.grad(loss(moe_lib.moe_share_ref_dense),
                             argnums=(0, 1)), in_axes=(None, 0))(params, xs)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=2e-5)
    np.testing.assert_allclose(share(params, cfg, xs[0]),
                               moe_lib.moe_share_ref_dense(params, cfg, xs[0]),
                               atol=1e-6)


def _nan_past_groups(real):
    """`ragged_dot` as the TPU's kernel may give it: the rows past the
    groups unwritten, here NaN, in the product and in its gradient with
    respect to the rows."""
    def fill(out, sizes):
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < jnp.sum(sizes), out, jnp.nan)

    @jax.custom_vjp
    def rd(lhs, rhs, sizes):
        return fill(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return rd(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: real(a, b, sizes), lhs, rhs)
        d_lhs, d_rhs = vjp(g)
        return fill(d_lhs, sizes), d_rhs, None

    rd.defvjp(fwd, bwd)
    return lambda lhs, rhs, group_sizes: rd(lhs, rhs, group_sizes)


def test_rows_past_the_held_groups_are_never_read(monkeypatch):
    """The rows of assignments to experts held elsewhere are never
    computed: whatever they hold (NaN here) reaches neither the output
    nor a gradient. A share of 2 of 8 experts, top-3, so that most rows
    lie past the held groups."""
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        _nan_past_groups(jax.lax.ragged_dot))
    cfg = _moe_cfg(experts_held=2, first_expert=4)
    params = moe_lib.moe_init(jax.random.PRNGKey(4), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 7, 32))
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, cfg, x)))
    share = lambda p, c, x: moe_lib.moe_share_apply(p, c, x)[0]
    np.testing.assert_allclose(share(params, cfg, x),
                               moe_lib.moe_share_ref_dense(params, cfg, x),
                               atol=1e-6)
    got = jax.grad(loss(share), argnums=(0, 1))(params, x)
    want = jax.grad(loss(moe_lib.moe_share_ref_dense),
                    argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, atol=2e-5)


def test_the_scanned_period_is_the_unrolled_stack():
    """Two periods of (window, full) as one scan with per-layer remat, and
    unrolled without remat: the same loss and gradients in float32."""
    cfg = dataclasses.replace(MELLUM.reduced(), num_layers=4,
                              dtype="float32")
    scanned = Transformer(cfg)
    unrolled = Transformer(dataclasses.replace(cfg, scan_layers=False,
                                               remat=False))
    params = scanned.init(jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 21), 0,
                                          cfg.vocab_size)}
    vg = lambda m: jax.value_and_grad(lambda p: m.loss(p, batch)[0])(params)
    (l1, g1), (l2, g2) = vg(scanned), vg(unrolled)
    assert l1 == pytest.approx(float(l2), rel=1e-6)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-4)


def test_the_config_has_its_nameplate_sizes():
    assert MELLUM.layer_types == ("window",) * 3 + ("full",)
    assert MELLUM.param_count() == pytest.approx(12.15e9, rel=0.01)
    assert MELLUM.active_param_count() == pytest.approx(2.44e9, rel=0.01)


def test_a_consuming_call_frees_the_callers_state():
    """`consume=True` gives the same rounds as a call that copies, and
    deletes the state it was handed."""
    from repro.config import FedConfig
    from repro.core import make_algorithm, run_rounds
    from repro.data import linreg_noniid
    from repro.models import LeastSquares

    model = LeastSquares(16)
    fed = FedConfig(algorithm="fedgia", num_clients=8, k0=5, alpha=0.5,
                    sigma_t=0.15, h_policy="diag_ema")
    algo = make_algorithm(fed, model.loss, model=model)
    batch = {k: jnp.asarray(v) for k, v in
             linreg_noniid(0, 800, 16, 8).items()}
    init = lambda: algo.init(model.init(jax.random.PRNGKey(0)),
                             jax.random.PRNGKey(1), init_batch=batch)
    kept, handed = init(), init()
    a = run_rounds(algo, kept, batch, 3)
    b = run_rounds(algo, handed, batch, 3, consume=True)
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        np.testing.assert_array_equal(x, y)
    assert not any(l.is_deleted() for l in jax.tree.leaves(kept["z"]))
    assert all(l.is_deleted() for l in jax.tree.leaves(handed["z"]))
