"""Device busy time per round outside the kernels and the collectives the
cell's configuration names (`kernels`: fedgia_update; `collectives`: the
chips' all-reduces), in ms (mean over chips): the per-client gradient,
eq. (11)'s local sums, the diagonal-H update, the participant draw and
the round's metrics, as XLA fusions."""


def read(r):
    if r.rounds <= 0:
        return None
    return 1e3 * r.outside_s() / r.rounds
