"""Shared HLO collective-budget guard for the sharded subprocess tests.

Every sharded integration test asserts the round's collective budget by
lowering the round fn and counting MODEL-SIZE collectives in the compiled
HLO text. Before this module each test carried its own copy of the regex;
now they all share one classifier:

  * `collective_counts(txt)` — {kind: count} over all-reduce /
    reduce-scatter / all-gather ops whose result shape is array-like
    (model-size), skipping the scalar/tuple-of-scalar riders.
  * `assert_barrier_round(txt)` — the PR-5 contract: eq. (11) + all
    diagnostics ride exactly ONE model-size all-reduce, and the round
    issues no model-size reduce-scatter/all-gather.
  * `assert_overlap_round(txt)` — the overlap="scatter" contract: ZERO
    model-size all-reduces; the round's model-size traffic is exactly one
    reduce-scatter (this round's contribution, issued early) plus one
    all-gather (last round's consensus shard, consumed at the top).

This module is imported both by the pytest process and INSIDE the
subprocess scripts (fake 8-device runs), so `conftest.fake_device_env`
puts the tests directory on the subprocess PYTHONPATH.

"Model-size" = the HLO result shape contains a dimensioned array
(`[<digit>` somewhere in the shape string). The scalar psum riders
(loss mean, |g|^2, participant count) lower to `f32[]` tuples and are
deliberately NOT counted — the guard is about wire traffic proportional
to the model, not O(1) control scalars.
"""
from __future__ import annotations

import re

KINDS = ("all-reduce", "reduce-scatter", "all-gather")

# `= <shape> <kind>(` — the result shape is either a bare `f32[...]` term
# or a tuple `(f32[...], f32[...])` for multi-operand collectives.
_COLLECTIVE_RE = re.compile(
    r"= ((?:\([^)]*\))|\S+) (all-reduce|reduce-scatter|all-gather)\(")


def is_model_size(shape: str) -> bool:
    """True when the HLO result shape string holds at least one
    dimensioned array (e.g. `f32[8,320]`), False for scalars (`f32[]`)
    and tuples of scalars."""
    return re.search(r"\[\d", shape) is not None


def collective_counts(txt: str, *, model_size_only: bool = True) -> dict:
    """Count collectives by kind in compiled HLO text.

    With `model_size_only` (default) only ops whose result shape carries a
    dimensioned array are counted — the scalar riders are free."""
    counts = {k: 0 for k in KINDS}
    for shape, kind in _COLLECTIVE_RE.findall(txt):
        if model_size_only and not is_model_size(shape):
            continue
        counts[kind] += 1
    return counts


def model_size_all_reduces(txt: str) -> int:
    """The historical single-number guard: model-size all-reduce count."""
    return collective_counts(txt)["all-reduce"]


_LOCATION_SECTIONS = ("FileNames", "FunctionNames", "FileLocations",
                      "StackFrames")


def program_text(txt: str) -> str:
    """Compiled HLO text with the source-location side tables, the
    per-op `metadata={...}` and the module header line removed: two
    programs that compute the same thing from different call sites
    compare equal, while any changed instruction still shows."""
    blocks = [b for b in txt.split("\n\n")
              if not b.startswith(("HloModule",) + _LOCATION_SECTIONS)]
    return re.sub(r", metadata=\{[^}]*\}", "", "\n\n".join(blocks))


def assert_barrier_round(txt: str, label: str = "") -> None:
    """The one-psum round (PR-5): exactly ONE model-size all-reduce, no
    all-gather. XLA additionally lowers the shard-local diagnostics
    reduction to at most one small reduce-scatter (result is 1/shards of
    the model) — tolerated, it predates the overlap work and is not a
    second model-size transfer."""
    c = collective_counts(txt)
    ok = (c["all-reduce"] == 1 and c["all-gather"] == 0
          and c["reduce-scatter"] <= 1)
    assert ok, (
        f"barrier round collective budget violated"
        f"{' (' + label + ')' if label else ''}: {c}")


def assert_overlap_round(txt: str, label: str = "") -> None:
    """The overlapped round: ZERO model-size all-reduces; one
    reduce-scatter (contribution, early) + one all-gather (consensus
    shard, deferred to the round top)."""
    c = collective_counts(txt)
    assert c == {"all-reduce": 0, "reduce-scatter": 1, "all-gather": 1}, (
        f"overlap round collective budget violated{' (' + label + ')' if label else ''}: {c}")
