"""Device-idle time inside the benchmark's span of each run_rounds call,
per call, in ms: run_rounds' own tracing, lowering, cache lookup, chunk
syncs and history transfer, as far as the chip waits on them. Moves
solve_s."""


def read(r):
    return 1e3 * r.idle_in_spans_s() / r.calls
