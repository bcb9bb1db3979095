"""The readers of `run_rounds`' own spans (`bench/program_spans.py`), on
events made by hand and on a paper_v1.solve window recorded on a TPU v5e."""
import gzip
import json
import os

import pytest

from bench import program_spans, run, trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SPAN_METRICS = ("driver_prepare_ms.solve", "driver_lower_ms.solve",
                "driver_compile_ms.solve", "driver_fetch_ms.solve",
                "lowerings_per_call.solve", "driver_unspanned_ms.solve")


def _spanned_trace():
    # two calls: the first lowers and compiles one chunk length, the
    # second two; a lowering outside the benchmark's spans (set-up) does
    # not count
    ops = [("fusion.1", 150, 180),   # an eager op inside prepare
           ("chunk", 720, 880), ("copy.1", 985, 995),
           ("chunk", 2520, 2890)]
    spans = [(tr.SPAN, 0, 1000), (tr.SPAN, 2000, 3000)]
    host = [("run_rounds", 10, 990), ("run_rounds.prepare", 10, 200),
            ("run_rounds.lower", 200, 500), ("run_rounds.compile", 500, 700),
            ("run_rounds.fetch", 900, 980),
            ("run_rounds.lower", 1200, 1300),
            ("run_rounds", 2010, 2990), ("run_rounds.prepare", 2010, 2100),
            ("run_rounds.lower", 2100, 2300),
            ("run_rounds.compile", 2300, 2400),
            ("run_rounds.lower", 2400, 2450),
            ("run_rounds.compile", 2450, 2500),
            ("run_rounds.fetch", 2900, 2950)]
    return tr.Trace({0: ops}, spans, sorted(host, key=lambda e: e[1]))


def _reading(trace, calls=2):
    return tr.Reading(trace, chips=1, rounds=0, calls=calls,
                      flops_per_round=1.0, kernel_bytes_per_round=1.0,
                      peaks=PEAKS)


def _read(r, names=SPAN_METRICS):
    spec = run.Spec()
    return {n: spec.reader(n).read(r) for n in names}


def test_span_readers_by_hand():
    got = _read(_reading(_spanned_trace()))
    ms = 1e-6  # ms per ns
    assert got["driver_prepare_ms.solve"] == pytest.approx((190 + 90) / 2 * ms)
    assert got["driver_lower_ms.solve"] == pytest.approx((300 + 250) / 2 * ms)
    assert got["driver_compile_ms.solve"] == pytest.approx(
        (200 + 150) / 2 * ms)
    assert got["driver_fetch_ms.solve"] == pytest.approx((80 + 50) / 2 * ms)
    assert got["lowerings_per_call.solve"] == 1.5
    # idle no phase covers: [0, 10) [700, 720) [880, 900) [980, 985)
    # [995, 1000) in the first call; [2000, 2010) [2500, 2520)
    # [2890, 2900) [2950, 3000) in the second
    assert got["driver_unspanned_ms.solve"] == pytest.approx(
        (60 + 90) / 2 * ms)


def test_phases_and_unspanned_idle_add_up_to_the_gap():
    r = _reading(_spanned_trace())
    gap = run.Spec().reader("driver_gap_ms.solve").read(r)
    # the phases cover all idle time but the unspanned part, and 30 ns of
    # the prepare span is the eager op's busy time
    phases = sum(_read(r)[n] for n in SPAN_METRICS[:4])
    assert gap == pytest.approx(phases - 30 / 2 * 1e-6
                                + _read(r)["driver_unspanned_ms.solve"])


@pytest.mark.parametrize("host", [
    [("PjitFunction(chunk_fn)", 50, 100), ("lower", 500, 700)],
    [("run_rounds.prepare", 60, 100), ("run_rounds.lower", 100, 300)],
], ids=["no_program_spans", "phases_without_the_call_span"])
def test_span_readers_return_nothing_without_run_rounds(host):
    t = tr.Trace({0: [("fusion.1", 100, 200), ("fusion.1", 700, 800)]},
                 [(tr.SPAN, 50, 500), (tr.SPAN, 600, 1100)], host)
    r = _reading(t)
    assert not program_spans.recorded(r)
    assert _read(r) == {n: None for n in SPAN_METRICS}


def _recorded(name):
    with gzip.open(os.path.join(HERE, "traces", name + ".json.gz"),
                   "rt") as f:
        rec = json.load(f)
    return rec, tr.Trace({int(k): [tuple(e) for e in v]
                          for k, v in rec["devices"].items()},
                         [tuple(e) for e in rec["spans"]],
                         [tuple(e) for e in rec["host"]])


def test_a_solve_recorded_on_a_v5e():
    # the traced window of paper_v1.solve: whole solves, each its own
    # run_rounds call inside the benchmark's span, with the program's spans
    rec, trace = _recorded("paper_v1.solve")
    for raw in rec["raw_names"]:
        assert raw.startswith("%") and " = " in raw
    spec = run.Spec()
    cell = spec.cell("paper_v1.solve")
    cfg = spec.config(cell)
    from bench import counting

    r = tr.Reading(trace, chips=1, rounds=0, calls=len(trace.spans),
                   peaks=PEAKS, kernels=cfg["kernels"],
                   **counting.for_config(cfg))
    metrics = spec.metrics("per_layer", cell)
    got = tr.collect(r, {m["name"]: spec.reader(m["name"]) for m in metrics})
    assert set(got) == {m["name"] for m in metrics}
    assert got["lowerings_per_call.solve"] == 1.0
    phases = sum(got[n] for n in SPAN_METRICS[:4])
    # the four phases hold the gap; what no span covers is small
    assert 0.9 * got["driver_gap_ms.solve"] < phases \
        < 1.1 * got["driver_gap_ms.solve"]
    assert 0 <= got["driver_unspanned_ms.solve"] \
        < 0.1 * got["driver_gap_ms.solve"]
