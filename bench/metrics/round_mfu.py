"""The whole round's share of the chips' peak, in %: the operations a
round requires (bench/counting.round_flops) times the rounds per second
of the traced window, over chips times the bf16 peak. The linear models
multiply in float32 at Precision.HIGHEST, which the MXU runs in several
bf16 passes; the peak is the bf16 one all the same."""


def read(r):
    if r.rounds <= 0:
        return None
    rate = r.flops_per_round * r.rounds / r.window_s
    return 100.0 * rate / (r.chips * r.peaks["bf16_flops_per_s"])
