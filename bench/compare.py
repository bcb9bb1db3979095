"""The comparison that decides `correct`: what the timed call produced
against the plain reference (`bench/reference.py`) over the same rounds.

Four numbers; a cell compares those its limits file
(`bench/limits/<cell>.json`, readings in PERF.md) gives a limit:

- loss_gap: the widest relative gap of the per-round f̄;
- grad_gap: the widest relative gap of the per-round ‖∇f(x̄)‖², over the
  rounds where the reference's value is at or above the configuration's
  `grad_floor` (below the eq. (35) target, float32 round-off sets it);
- state_gap: the widest gap of the state after the call: per client row
  of each per-client buffer the reference returns (for FedGiA z, π and
  H), ‖program − reference‖ over the larger of the reference row's norm
  and the median row norm, and the same for x̄ as one row;
- selected_gap: the widest gap of the per-round participant count (exact).
"""
from __future__ import annotations

import numpy as np

from bench.reference import by_rows

NUMBERS = ("loss_gap", "grad_gap", "state_gap", "selected_gap")
HISTORY = ("f_xbar", "grad_sq_norm", "selected")  # per round
NON_FINITE = 1e300  # a gap that is not a number reads as this


def _finite(v: float) -> float:
    return float(v) if np.isfinite(v) else NON_FINITE


def _row_norms(prog: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """(‖program − reference‖, ‖reference‖) of each row, in float64."""
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    return np.stack([np.linalg.norm(prog - ref, axis=1),
                     np.linalg.norm(ref, axis=1)], axis=1)


def row_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    prog = np.reshape(prog, (-1, np.shape(ref)[-1]))
    diff, norm = by_rows(_row_norms, prog, np.reshape(ref, prog.shape)).T
    scale = np.maximum(norm, np.median(norm))
    scale = np.where(scale > 0, scale, 1.0)
    if not np.all(np.isfinite(diff)):
        return NON_FINITE
    return _finite(np.max(diff / scale))


def _rel(prog, ref) -> np.ndarray:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    gap = np.abs(prog - ref) / np.maximum(np.abs(ref), 1e-30)
    return np.where(np.isfinite(gap), gap, NON_FINITE)


def numbers(prog: dict, ref: dict, grad_floor: float) -> dict:
    """The four numbers; `prog` holds the keys of `ref` (HISTORY per
    round; x̄ and the per-client buffers after it)."""
    t = len(ref["f_xbar"])
    if len(prog["f_xbar"]) != t:
        return {k: NON_FINITE for k in NUMBERS}
    use = np.asarray(ref["grad_sq_norm"]) >= grad_floor
    grad = _rel(prog["grad_sq_norm"], ref["grad_sq_norm"])[use]
    state = row_gap(prog["x"][None], ref["x"][None])
    for k in set(ref) - set(HISTORY) - {"x"}:
        state = max(state, row_gap(prog[k], ref[k]))
    return {
        "loss_gap": _finite(np.max(_rel(prog["f_xbar"], ref["f_xbar"]))),
        "grad_gap": _finite(np.max(grad)) if grad.size else 0.0,
        "state_gap": state,
        "selected_gap": _finite(np.max(np.abs(
            np.asarray(prog["selected"], np.float64)
            - np.asarray(ref["selected"], np.float64)))),
    }


def compared(limits: dict) -> list:
    """The numbers a cell compares, in NUMBERS order."""
    names = [k for k in NUMBERS if k in limits]
    if not names:
        raise KeyError(f"limits file gives none of {NUMBERS}")
    return names


def verdict(nums: dict, limits: dict) -> bool:
    return all(nums[k] <= limits[k] for k in compared(limits))


def report(nums: dict, limits: dict) -> dict:
    """{name: {"value": number, "limit": limit}} of the compared numbers."""
    return {k: {"value": nums[k], "limit": limits[k]}
            for k in compared(limits)}
