"""Host ms per call in run_rounds' `run_rounds.compile` spans: the
persistent-cache load, or the XLA compile, of each lowered chunk
program. Read as `driver_compile_ms.solve` (moves solve_s); nothing
where the program records no spans."""
from bench import program_spans


def read(r):
    return program_spans.phase_ms(r, "run_rounds.compile")
