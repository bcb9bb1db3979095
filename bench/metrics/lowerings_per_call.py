"""Chunk programs lowered per run_rounds call: `run_rounds.lower` spans
over calls. Read as `lowerings_per_call.solve` (moves solve_s); nothing
where the program records no spans."""
from bench import program_spans


def read(r):
    return program_spans.per_call(r, "run_rounds.lower")
