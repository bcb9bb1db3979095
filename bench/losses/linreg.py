"""Least squares, the paper's Example V.1, in plain terms for the
reference: f_i(x) = 1/(2 d_i) ||A_i x - b_i||^2."""
import numpy as np


def terms(cfg: dict, z, b):
    """(per-sample loss, its derivative in z = a·x)."""
    return 0.5 * (z - b) ** 2, z - b


def regulariser(cfg: dict, x, d):
    """(per-client loss term, per-client gradient term): none."""
    return 0.0, 0.0


def lipschitz(cfg: dict, top: np.ndarray, d: np.ndarray) -> float:
    """r = max_i ||A_iᵀ A_i||₂ / d_i."""
    return float(np.max(top / d))
