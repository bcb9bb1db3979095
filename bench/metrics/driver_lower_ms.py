"""Host ms per call in run_rounds' `run_rounds.lower` spans: a fresh
jax.jit of each AOT chunk length and its trace to StableHLO. Read as
`driver_lower_ms.solve` (moves solve_s); nothing where the program
records no spans."""
from bench import program_spans


def read(r):
    return program_spans.phase_ms(r, "run_rounds.lower")
