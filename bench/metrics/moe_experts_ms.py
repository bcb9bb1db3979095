"""Device time of the expert layers' grouped products per round, in ms
(mean over chips): the ops of the configuration's `moe_experts` kernel
pattern, forward, recomputed and backward. Moves rounds_per_s. Nothing
to read where the cell's path runs no such kernel."""


def read(r):
    t = r.kernel_s("moe_experts")
    if t is None or r.rounds <= 0:
        return None
    return 1e3 * t / r.rounds
