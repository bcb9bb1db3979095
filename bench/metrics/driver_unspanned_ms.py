"""Device-idle ms per call inside the benchmark's span of each
run_rounds call that none of run_rounds' phase spans (prepare, lower,
compile, fetch) covers: the chunk dispatches and host syncs, and the
benchmark's own work around the call. Read as
`driver_unspanned_ms.solve` (moves solve_s); nothing where the program
records no spans."""
from bench import program_spans


def read(r):
    return program_spans.unspanned_idle_ms(r)
