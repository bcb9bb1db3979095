"""Plain reference of FedGiA's round (arXiv:2205.01438, Algorithm 1).

Written from the paper and the configuration file alone: it imports
nothing of `repro` and takes nothing the program made. Float32 `jnp`,
every product at `Precision.HIGHEST`, no kernel, no flat buffers, no
closed form: the ADMM branch runs the paper's k0 iterations of eqs.
(12)-(14) one by one. Per round t:

  x̄      = (1/m) Σ_i z_i                                    eq. (11)
  g_i    = ∇f_i(x̄),  ḡ_i = g_i / m
  C      = the round's participants (drawn as the configuration says)
  i ∈ C:  k0 times  x_i = x̄ − D_i (ḡ_i + π_i),  π_i += σ (x_i − x̄)
          z_i = x_i + π_i / σ,   D_i = (H_i/m + σ I)^{-1}   eqs. (12)-(14)
  i ∉ C:  π_i = −ḡ_i,  z_i = x̄ − ḡ_i / σ                     eqs. (15)-(17)
  H_i    = clip(0.9 H_i + 0.1 r g_i² / max_j,l g_jl², 0, r)  (diagonal EMA)

with σ = σ_t r / m and r from `bench/losses/<problem>.py`, worked out on
the host in float64. Reported per round at x̄: f̄ = mean_i f_i(x̄),
‖(1/m) Σ_i g_i‖² and |C|.

x, z, π and H are flat: a model's parameters in the order of their
pytree's leaves, each leaf raveled. Where the cell lies over a mesh, the
same program runs with its data laid by rows over it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref

EMA_BETA = 0.9
H_POLICIES = ("diag_ema",)


def _flat(tree, lead: int):
    """A parameter pytree raveled leaf by leaf, after `lead` leading axes;
    a single leaf is only reshaped (for one (m, n) leaf: itself)."""
    leaves = [l.reshape(l.shape[:lead] + (-1,)) for l in jax.tree.leaves(tree)]
    return leaves[0] if len(leaves) == 1 else jnp.concatenate(leaves, -1)


def program_state(state) -> dict:
    """The program's state after a call, under the reference's names: x̄
    (n,) and the per-client rows z, π, H (m, n)."""
    out = {"x": _flat(state["x"], 0)}
    for k in ("z", "pi", "h"):
        out[k] = _flat(state[k], 1)
    return out


def run(cfg: dict, data: dict, rounds: int, selection: dict,
        precision: str = "highest", mesh=None, x0=None) -> dict:
    """`rounds` rounds from x = z = x⁰ (the paper's 0 where `x0` is
    None), π = 0, H = r I. Returns host arrays: per-round "f_xbar",
    "grad_sq_norm", "selected", and the state after the last round, "x"
    (n,), "z", "pi", "h" (m, n)."""
    if precision not in ref.PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {ref.PRECISIONS}")
    if cfg["h_policy"] not in H_POLICIES:
        raise ValueError(f"h_policy {cfg['h_policy']!r} has no reference "
                         f"(only {H_POLICIES})")
    m, n, k0 = cfg["num_clients"], cfg["dim"], cfg["k0"]
    r = np.float32(ref.lipschitz(cfg, data))
    sigma = np.float32(cfg["sigma_t"] * float(r) / m)
    key0, next_mask = ref.masks(selection, m)

    def one_round(data_d, r, sigma, carry, t):
        z, pi, h, key = carry
        xbar = jnp.sum(z, axis=0) / m
        f, g = ref.loss_grad(cfg, data_d, xbar, precision)
        gb = g / m
        sel, key = next_mask(key, t)
        D = 1.0 / (h / m + sigma)
        pi_a = pi
        for _ in range(k0):
            x_a = xbar[None, :] - D * (gb + pi_a)
            pi_a = pi_a + sigma * (x_a - xbar[None, :])
        z_a = x_a + pi_a / sigma
        z_g = xbar[None, :] - gb / sigma
        pick = sel[:, None]
        z2 = jnp.where(pick, z_a, z_g)
        pi2 = jnp.where(pick, pi_a, -gb)
        g2 = jnp.square(g)
        gmax = jnp.maximum(jnp.max(g2), 1e-30)
        h2 = jnp.clip(EMA_BETA * h + (1.0 - EMA_BETA) * (r * g2 / gmax),
                      0.0, r)
        gmean = jnp.sum(g, axis=0) / m
        met = (jnp.mean(f), jnp.sum(jnp.square(gmean)),
               jnp.sum(sel.astype(jnp.int32)))
        return (z2, pi2, h2, key), (met, xbar)

    # r and sigma are arguments, not constants of the program: r follows
    # the data, and a program that held it would compile anew for every
    # seed whose data differ
    @jax.jit
    def scan(data_d, key, r, sigma, x0):
        zeros = jnp.zeros((m, n), jnp.float32)
        z0 = zeros if x0 is None else jnp.broadcast_to(x0, (m, n))
        carry = (z0, zeros, jnp.full((m, n), r, jnp.float32), key)
        (z, pi, h, _), (met, xbars) = jax.lax.scan(
            lambda c, t: one_round(data_d, r, sigma, c, t), carry,
            jnp.arange(rounds))
        return z, pi, h, met, xbars[-1]

    data_d = ref.device_data(cfg, data, mesh)
    if x0 is not None:
        x0 = jnp.asarray(x0, jnp.float32)
    z, pi, h, (f, gsq, sel), x = scan(data_d, key0, r, sigma, x0)
    del data_d
    out = {"f_xbar": f, "grad_sq_norm": gsq, "selected": sel,
           "x": x, "z": z, "pi": pi, "h": h}
    return {k: np.asarray(jax.device_get(v)) for k, v in out.items()}
