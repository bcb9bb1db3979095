"""The benchmark's copy of the repo's `linreg_noniid` (from
`repro/data/synthetic.py`): the paper's Example V.1, least squares on
features drawn from a mixture of three distributions, split over
clients of heterogeneous sizes. Copied so that no later change to the
program can move the traffic; `bench/test_bench_generators.py` pins it
to the original. Host (numpy) arrays; the caller puts them on the
device.
"""
from __future__ import annotations

import numpy as np


def _mixture_features(rng: np.random.Generator, d: int, n: int) -> np.ndarray:
    thirds = [d // 3, d // 3, d - 2 * (d // 3)]
    parts = [
        rng.standard_normal((thirds[0], n)),
        rng.standard_t(df=5, size=(thirds[1], n)),
        rng.uniform(-5.0, 5.0, size=(thirds[2], n)),
    ]
    A = np.concatenate(parts, axis=0)
    rng.shuffle(A, axis=0)
    return A.astype(np.float32)


def linreg_noniid(seed: int, d: int, n: int, m: int) -> dict:
    """d samples of n features over m clients of heterogeneous sizes,
    b = A x* + 0.1 noise: A (m, dmax, n), b (m, dmax), mask (m, dmax)."""
    rng = np.random.default_rng(seed)
    A = _mixture_features(rng, d, n)
    x_star = rng.standard_normal(n).astype(np.float32)
    b = A @ x_star + 0.1 * rng.standard_normal(d).astype(np.float32)
    sizes = heterogeneous_sizes(rng, d, m)
    return client_batches({"A": A, "b": b}, sizes)


def heterogeneous_sizes(rng: np.random.Generator, d: int, m: int) -> list:
    """d_i ~ uniform{floor(0.5 d/m) .. ceil(1.5 d/m)}, summing to d."""
    base = d / m
    lo, hi = max(1, int(0.5 * base)), max(2, int(1.5 * base))
    sizes = rng.integers(lo, hi + 1, size=m)
    while sizes.sum() > d:
        cand = np.flatnonzero(sizes > lo)
        sizes[rng.choice(cand if len(cand) else np.arange(m))] -= 1
    while sizes.sum() < d:
        cand = np.flatnonzero(sizes < hi)
        sizes[rng.choice(cand if len(cand) else np.arange(m))] += 1
    return np.maximum(sizes, 1).tolist()


def client_batches(data: dict, sizes: list) -> dict:
    """Split row-wise into len(sizes) clients, pad to the largest, add a
    mask of the real rows."""
    dmax = max(sizes)
    out = {k: [] for k in data}
    masks = []
    start = 0
    for s in sizes:
        for k, v in data.items():
            chunk = v[start:start + s]
            pad = [(0, dmax - s)] + [(0, 0)] * (chunk.ndim - 1)
            out[k].append(np.pad(chunk, pad))
        mask = np.zeros(dmax, np.float32)
        mask[:s] = 1.0
        masks.append(mask)
        start += s
    batch = {k: np.stack(v) for k, v in out.items()}
    batch["mask"] = np.stack(masks)
    return batch


def make(cfg: dict, seed: int) -> dict:
    return linreg_noniid(seed, cfg["samples"], cfg["dim"], cfg["num_clients"])
