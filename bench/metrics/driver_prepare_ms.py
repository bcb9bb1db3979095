"""Host ms per call in run_rounds' `run_rounds.prepare` span: argument
checks, the ravel, the round function, the donated state copy, the
policy's state, the round's eval_shape and the carry, before the first
chunk program. Read as `driver_prepare_ms.solve` (moves solve_s);
nothing where the program records no spans."""
from bench import program_spans


def read(r):
    return program_spans.phase_ms(r, "run_rounds.prepare")
