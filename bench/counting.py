"""Operations and bytes one FedGiA round requires, from shapes alone.

`kernel_bytes`: what one call of the batched `fedgia_update` kernel
must move through HBM, at the problem's real width: it reads x̄
broadcast, ḡ, π and H (four (m, n) streams) and writes x, π and z
(three), each at the state's itemsize, plus the (m,) branch select as
int32 and two f32 scalars. The lanes the program pads n up to are not
counted: they are work the round does not need, so a kernel that packs
them away reads nearer its roofline, and one that pads wider does not
read better.

`round_flops`: what the round requires at the problem's real sizes
(n features, d samples over m clients; no lane padding):
- the per-client gradients: two matrix-vector products per sample,
  A_i x̄ and A_iᵀ r_i, 2 n operations each: 4 n d;
- the kernel's elementwise update, per client coordinate:
  D = 1/(H/m + σ) 3, a = 1 − σD 2, π + ḡ 1, a^(k0−1) by repeated
  squaring, π' = a^(k0−1)·a·base − ḡ 3, x' = x̄ − D·a^(k0−1)·base 3,
  z' = x' + π'/σ 2;
- per client coordinate besides: eq. (11)'s mean 1, ḡ = g/m 1, the
  ‖∇f‖² mean 1, and the diagonal-H update (g·m)² 2 and
  0.9 H + 0.1 r g²/max 5.
Elementwise work at the sample level (residuals, sigmoids) is not
counted. The linear models multiply in float32 at `Precision.HIGHEST`;
the peak they are held against is the chip's bf16 one.

A problem whose gradient is not the linear one gives its own counts:
`counts(cfg)` in `bench/losses/<problem>.py`, with the keys of
`for_config`.
"""
from __future__ import annotations

from bench import reference

def int_pow_mults(p: int) -> int:
    """Multiplies of x**p by repeated squaring (p >= 1)."""
    return p.bit_length() - 1 + bin(p).count("1") - 1


def kernel_elementwise_ops(k0: int) -> int:
    return 3 + 2 + 1 + int_pow_mults(k0 - 1) + 3 + 3 + 2


ROUND_ELEMENTWISE_OPS = 1 + 1 + 1 + 2 + 5


def kernel_bytes(m: int, n: int, itemsize: int = 4) -> int:
    return 7 * m * n * itemsize + m * 4 + 2 * 4


def round_flops(m: int, n: int, d: int, k0: int) -> int:
    return 4 * n * d + m * n * (kernel_elementwise_ops(k0)
                                + ROUND_ELEMENTWISE_OPS)


def for_config(cfg: dict) -> dict:
    """{"flops_per_round", "kernel_bytes_per_round"} of a configuration:
    the problem file's `counts(cfg)` where it gives them, else those of
    FedGiA over a linear model."""
    own = getattr(reference.load("losses", cfg["problem"]), "counts", None)
    if own:
        return own(cfg)
    m, n = cfg["num_clients"], cfg["dim"]
    d = cfg.get("samples", m)
    return {"flops_per_round": round_flops(m, n, d, cfg["k0"]),
            "kernel_bytes_per_round": kernel_bytes(m, n)}
