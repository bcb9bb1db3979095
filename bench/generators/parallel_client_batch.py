"""One least-squares sample per client, drawn as `million_client_batch`
draws them (A and x* standard normal, b = A x* + 0.1 noise), but in
blocks of BLOCK clients on the host's cores: x* from the first child of
the seed's `SeedSequence`, block j from child j + 1, so the arrays
depend on the seed alone and not on the number of cores. One stream
draws 8·10^6 clients in some 30 s, which every run's set-up would pay.
Host (numpy) arrays; the caller puts them on the device.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 16


def parallel_client_batch(m: int, n: int, seed: int) -> dict:
    """A (m, 1, n), b (m, 1) and an all-ones mask (m, 1), float32."""
    blocks = -(-m // BLOCK)
    x_seq, *seqs = np.random.SeedSequence(seed).spawn(1 + blocks)
    x_star = np.random.default_rng(x_seq).standard_normal(n, np.float32)
    A = np.empty((m, 1, n), np.float32)
    b = np.empty((m, 1), np.float32)

    def fill(j):
        lo, hi = j * BLOCK, min(m, (j + 1) * BLOCK)
        rng = np.random.default_rng(seqs[j])
        A[lo:hi] = rng.standard_normal((hi - lo, 1, n), np.float32)
        b[lo:hi] = (A[lo:hi, 0, :] * x_star).sum(axis=1, keepdims=True) \
            + 0.1 * rng.standard_normal((hi - lo, 1), np.float32)

    with ThreadPoolExecutor(min(blocks, os.cpu_count() or 1)) as ex:
        list(ex.map(fill, range(blocks)))
    return {"A": A, "b": b, "mask": np.ones((m, 1), np.float32)}


def make(cfg: dict, seed: int) -> dict:
    return parallel_client_batch(cfg["num_clients"], cfg["dim"], seed)
