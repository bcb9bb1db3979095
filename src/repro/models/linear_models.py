"""The paper's own test models (§V Examples V.1–V.3).

These are the exact objectives FedGiA is evaluated on in the paper, so the
numerical reproduction (benchmarks/table4.py etc.) uses them directly. Each
model exposes the same protocol as Transformer.loss: loss(params, batch) ->
(loss, metrics); params here is {"x": (n,)}.

Losses follow the paper's normalisation: per-client
  f_i(x) = (1/d_i) sum_j loss_j  (+ regulariser / d_i)

Every product runs at full f32 precision (`_mm`). A TPU's default
precision multiplies f32 operands in one bf16 pass, and the gradient
noise that leaves puts ||grad f||^2 above the eq. (35) tolerance of the
logistic problems: the run never stops. The CPU computes f32 either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_mm = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)


class LeastSquares:
    """Example V.1:  f_i(x) = 1/(2 d_i) ||A_i x - b_i||^2."""

    def __init__(self, n: int):
        self.n = n

    def init(self, rng):
        return {"x": jnp.zeros((self.n,), jnp.float32)}

    def loss(self, params, batch):
        A, b = batch["A"], batch["b"]
        mask = batch.get("mask")
        r = _mm(A, params["x"]) - b
        if mask is None:
            loss = 0.5 * jnp.mean(jnp.square(r))
        else:
            loss = 0.5 * jnp.sum(mask * jnp.square(r)) / jnp.maximum(mask.sum(), 1.0)
        return loss, {"loss": loss}

    def gram(self, batch):
        """H_i = B_i / d_i with B_i = A_i^T A_i (paper Table III, Ex. V.1)."""
        A, d = _masked(batch)
        return _mm(A.T, A) / d

    def lipschitz(self, batch):
        """r_i = ||B_i|| / d_i (spectral norm of the Hessian)."""
        H = self.gram(batch)
        return jnp.linalg.norm(H, ord=2)


def _masked(batch):
    """Apply the ragged-client mask: zero padded rows, return effective d_i."""
    A = batch["A"]
    mask = batch.get("mask")
    if mask is None:
        return A, A.shape[0]
    return A * mask[:, None], jnp.maximum(mask.sum(), 1.0)


class LogisticRegression:
    """Example V.2:  l2-regularised logistic loss,
    f_i(x) = (1/d_i) sum_j [ln(1+e^{<a,x>}) - b<a,x>] + mu/(2 d_i) ||x||^2."""

    def __init__(self, n: int, mu: float = 1e-3):
        self.n = n
        self.mu = mu

    def init(self, rng):
        return {"x": jnp.zeros((self.n,), jnp.float32)}

    def loss(self, params, batch):
        A, b = batch["A"], batch["b"]
        mask = batch.get("mask")
        z = _mm(A, params["x"])
        per = jnp.logaddexp(0.0, z) - b * z
        if mask is None:
            d = A.shape[0]
            ll = jnp.sum(per) / d
        else:
            d = jnp.maximum(batch["mask"].sum(), 1.0)
            ll = jnp.sum(mask * per) / d
        reg = 0.5 * self.mu * jnp.sum(jnp.square(params["x"])) / d
        loss = ll + reg
        return loss, {"loss": loss}

    def gram(self, batch):
        """H_i = B_i/(4 d_i) (paper Table III, Ex. V.2): sigmoid' <= 1/4."""
        A, d = _masked(batch)
        return _mm(A.T, A) / (4.0 * d)

    def lipschitz(self, batch):
        _, d = _masked(batch)
        return jnp.linalg.norm(self.gram(batch), ord=2) + self.mu / d


class NonConvexLogistic:
    """Example V.3: logistic loss + non-convex regulariser
    mu/(2 d_i) sum_l x_l^2 / (1 + x_l^2)."""

    def __init__(self, n: int, mu: float = 1e-2):
        self.n = n
        self.mu = mu

    def init(self, rng):
        return {"x": jnp.zeros((self.n,), jnp.float32)}

    def loss(self, params, batch):
        A, b = batch["A"], batch["b"]
        mask = batch.get("mask")
        x = params["x"]
        z = _mm(A, x)
        per = jnp.logaddexp(0.0, z) - b * z
        if mask is None:
            d = A.shape[0]
            ll = jnp.sum(per) / d
        else:
            d = jnp.maximum(mask.sum(), 1.0)
            ll = jnp.sum(mask * per) / d
        x2 = jnp.square(x)
        reg = 0.5 * self.mu * jnp.sum(x2 / (1.0 + x2)) / d
        loss = ll + reg
        return loss, {"loss": loss}

    def gram(self, batch):
        """Paper Table III, Ex. V.3: B_i/(4 d_i) + mu I / d_i."""
        A, d = _masked(batch)
        return _mm(A.T, A) / (4.0 * d) + self.mu * jnp.eye(self.n) / d

    def lipschitz(self, batch):
        return jnp.linalg.norm(self.gram(batch), ord=2)
