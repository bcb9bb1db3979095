"""`run_rounds`' own host spans in a traced window, for the readers in
`bench/metrics/` that split its per-call host time.

`core/engine.py`'s `run_rounds` records `run_rounds` around each call
and, inside it, the phases `run_rounds.prepare` (checks, ravel, state
copy, carry), `run_rounds.lower` and `run_rounds.compile` (one of each
per AOT-compiled chunk length) and `run_rounds.fetch` (history and state
back to the host), as `jax.profiler.TraceAnnotation`s. They land among
the trace's host events (`Trace.host`) on the device ops' clock. Only the
parts inside the benchmark's spans count. Each function gives None where
the trace holds no `run_rounds` span: a program older than the spans.
"""
from __future__ import annotations

from typing import List, Optional

from bench import trace as tr

CALL = "run_rounds"
PHASES = ("run_rounds.prepare", "run_rounds.lower", "run_rounds.compile",
          "run_rounds.fetch")


def recorded(r) -> bool:
    return any(name == CALL for name, _, _ in r.trace.host)


def _in_calls(r, names) -> List[tr.Event]:
    """The host events named in `names`, clipped to the benchmark's
    spans."""
    events = [e for e in r.trace.host if e[0] in names]
    return [c for _, lo, hi in r.trace.spans for c in tr.clip(events, lo, hi)]


def phase_ms(r, name: str) -> Optional[float]:
    """Host ms per call inside the spans named `name`."""
    if not recorded(r):
        return None
    return 1e-6 * sum(e - s for _, s, e in _in_calls(r, (name,))) / r.calls


def per_call(r, name: str) -> Optional[float]:
    """Spans named `name` per call."""
    if not recorded(r):
        return None
    return len(_in_calls(r, (name,))) / r.calls


def unspanned_idle_ms(r) -> Optional[float]:
    """Device-idle ms per call inside the benchmark's spans that none of
    the phase spans covers (mean over chips): the part of the per-call
    gap that no phase explains."""
    if not recorded(r):
        return None
    phases = _in_calls(r, PHASES)

    def idle(ops):
        return sum((e - s) - tr.busy_ns(phases, s, e)
                   for _, lo, hi in r.trace.spans
                   for s, e in tr.gaps(ops, lo, hi))
    return 1e-6 * r.mean_ns(idle) / r.calls
