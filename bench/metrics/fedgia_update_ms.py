"""Device time of the fedgia_update kernel per round, in ms (mean over
chips). Nothing to read where the cell's path runs no such kernel."""


def read(r):
    t = r.kernel_s("fedgia_update")
    if t is None or r.rounds <= 0:
        return None
    return 1e3 * t / r.rounds
