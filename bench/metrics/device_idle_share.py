"""Share of the traced window in which no op ran on the chips (mean over
chips), in %: 1 − busy / window. Read as `device_idle_share.rounds`
(moves rounds_per_s) and `device_idle_share.solve` (moves solve_s)."""


def read(r):
    return 100.0 * (1.0 - r.busy_s / r.window_s)
