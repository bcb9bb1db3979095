"""Host ms per call in run_rounds' `run_rounds.fetch` span, after the
last chunk is done: the stop flag and the per-round history brought to
the host, and the state unflattened. Read as `driver_fetch_ms.solve`
(moves solve_s); nothing where the program records no spans."""
from bench import program_spans


def read(r):
    return program_spans.phase_ms(r, "run_rounds.fetch")
