"""Device time per round in the collectives between the cell's chips
(the configuration's `collectives` pattern: eq. (11)'s all-reduce and
the scalars that ride with it), in ms (mean over chips). Moves
rounds_per_s. Nothing to read on one chip, where no pattern is named."""


def read(r):
    t = r.collective_s()
    if t is None or r.rounds <= 0:
        return None
    return 1e3 * t / r.rounds
