"""Device busy time per round outside the kernels the cell's
configuration names (`kernels`: fedgia_update), in ms (mean over chips):
the per-client gradient, eq. (11)'s mean, the diagonal-H update and the
round's metrics, as XLA fusions."""


def read(r):
    if r.rounds <= 0:
        return None
    return 1e3 * (r.busy_s - r.kernel_s()) / r.rounds
