"""The benchmark's copy of `million_client_batch` (from
`benchmarks/common.py`): one least-squares sample per client. Copied so
that no later change to the program can move the traffic;
`bench/test_bench_generators.py` pins it to the original. Host (numpy)
arrays; the caller puts them on the device.
"""
from __future__ import annotations

import numpy as np


def million_client_batch(m: int, n: int, seed: int) -> dict:
    """A (m, 1, n), b (m, 1) and an all-ones mask (m, 1), float32."""
    rng = np.random.default_rng(seed)
    A = rng.standard_normal((m, 1, n)).astype(np.float32)
    x_star = rng.standard_normal(n).astype(np.float32)
    b = (A @ x_star + 0.1 * rng.standard_normal((m, 1))).astype(np.float32)
    return {"A": A, "b": b, "mask": np.ones((m, 1), np.float32)}


def make(cfg: dict, seed: int) -> dict:
    return million_client_batch(cfg["num_clients"], cfg["dim"], seed)
