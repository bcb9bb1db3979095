"""The reduction from trace to per-layer metrics, on events made by hand
and on a small trace recorded on a TPU v5e."""
import gzip
import json
import os
import re

import pytest

from bench import run, trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_union_busy_and_gaps():
    ev = [("a", 10, 20), ("b", 15, 30), ("c", 40, 50), ("d", 45, 48)]
    assert tr.union([(s, e) for _, s, e in ev]) == [(10, 30), (40, 50)]
    assert tr.busy_ns(ev, 0, 100) == 30
    assert tr.busy_ns(ev, 25, 45) == 10
    assert tr.gaps(ev, 0, 100) == [(0, 10), (30, 40), (50, 100)]
    assert tr.gaps(ev, 12, 45) == [(30, 40)]


def test_host_label_takes_the_widest_overlap():
    host = [("outer", 0, 100), ("compile", 20, 60), ("wait", 55, 70)]
    assert tr.host_label(host, 30, 50) == "compile"
    assert tr.host_label(host, 58, 70) == "wait"
    assert tr.host_label(host, 200, 210) == "host: no event"


def _hand_trace():
    # two calls of two rounds each: a kernel op and an XLA fusion per
    # round, and one other op
    k = "fedgia_update_batched_kernel_donated.1"  # the HLO op's name
    ops = [("fusion.1", 100, 200), (k, 200, 260),
           ("fusion.1", 300, 400), (k, 400, 460),
           ("all-reduce.3", 460, 480),
           ("fusion.1", 700, 800), (k, 800, 860),
           ("fusion.1", 860, 960), (k, 960, 1020)]
    spans = [(tr.SPAN, 50, 500), (tr.SPAN, 600, 1100)]
    host = [("PjitFunction(chunk_fn)", 50, 100), ("lower", 500, 700)]
    return tr.Trace({0: ops}, spans, host)


KERNELS = {"fedgia_update": r"^fedgia_update_batched_kernel"}


def _reading(trace, rounds=4, calls=2, kernels=KERNELS):
    return tr.Reading(trace, chips=1, rounds=rounds, calls=calls,
                      flops_per_round=1e6, kernel_bytes_per_round=1e4,
                      peaks=PEAKS, kernels=kernels)


def test_reading_by_hand():
    r = _reading(_hand_trace())
    assert r.window_s == pytest.approx(1050e-9)
    busy = 100 + 60 + 100 + 60 + 20 + 100 + 60 + 100 + 60
    assert r.busy_s == pytest.approx(busy * 1e-9)
    assert r.kernel_s() == pytest.approx(240e-9)
    # idle inside the spans: (450 - 340) + (500 - 320)
    assert r.idle_in_spans_s() == pytest.approx(290e-9)
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(400e-9)]
    longest = b["idle_gaps"][0]
    assert longest[0] == "lower" and longest[1] == pytest.approx(220e-9)


def test_metric_readers_by_hand():
    spec = run.Spec()
    r = _reading(_hand_trace())
    names = [m["name"] for m in spec.bench["per_layer"]]
    got = tr.collect(r, {n: spec.reader(n) for n in names})
    busy = 660e-9
    assert got["device_idle_share.rounds"] == pytest.approx(
        100 * (1 - busy / 1050e-9))
    assert got["driver_gap_ms.solve"] == pytest.approx(1e3 * 290e-9 / 2)
    assert got["fedgia_update_ms"] == pytest.approx(1e3 * 60e-9)
    assert got["xla_ops_ms"] == pytest.approx(1e3 * (busy - 240e-9) / 4)
    assert got["fedgia_update_roofline"] == pytest.approx(
        100 * 4e4 / (240e-9 * 819e9))
    assert got["round_mfu"] == pytest.approx(
        100 * 4e6 / 1050e-9 / 197e12)


def test_readers_of_a_kernel_the_path_does_not_run_return_nothing():
    t = _hand_trace()
    t.devices[0] = [e for e in t.devices[0] if "kernel" not in e[0]]
    spec = run.Spec()
    r = _reading(t, kernels={})
    for name in ("fedgia_update_ms", "fedgia_update_roofline"):
        assert spec.reader(name).read(r) is None
    assert spec.reader("xla_ops_ms").read(r) == pytest.approx(
        1e3 * 420e-9 / 4)


def test_collectives_by_hand():
    r = tr.Reading(_hand_trace(), chips=1, rounds=4, calls=2,
                   flops_per_round=1e6, kernel_bytes_per_round=1e4,
                   peaks=PEAKS, kernels=KERNELS, collectives=r"^all-reduce")
    spec = run.Spec()
    assert r.collective_s() == pytest.approx(20e-9)
    assert spec.reader("collective_ms").read(r) == pytest.approx(
        1e3 * 20e-9 / 4)
    # outside the kernel and the collective: the four fusions
    assert spec.reader("xla_ops_ms").read(r) == pytest.approx(
        1e3 * 400e-9 / 4)
    # one chip names no collective: nothing to read, xla_ops_ms as before
    r1 = _reading(_hand_trace())
    assert r1.collective_s() is None
    assert spec.reader("collective_ms").read(r1) is None
    assert spec.reader("xla_ops_ms").read(r1) == pytest.approx(
        1e3 * 420e-9 / 4)


def test_collectives_named_but_missing_from_the_trace_is_an_error():
    t = _hand_trace()
    t.devices[0] = [e for e in t.devices[0] if "all-reduce" not in e[0]]
    with pytest.raises(ValueError, match="no collective op"):
        tr.Reading(t, chips=1, rounds=4, calls=2, flops_per_round=1.0,
                   kernel_bytes_per_round=1.0, peaks=PEAKS, kernels=KERNELS,
                   collectives=r"^all-reduce")


def test_a_kernel_the_path_runs_missing_from_the_trace_is_an_error():
    t = _hand_trace()
    t.devices[0] = [e for e in t.devices[0] if "kernel" not in e[0]]
    with pytest.raises(ValueError, match="no op of the fedgia_update"):
        _reading(t)


def test_reading_needs_spans_and_chips():
    t = _hand_trace()
    with pytest.raises(ValueError, match="chips|TPU planes"):
        tr.Reading(t, chips=2, rounds=4, calls=2, flops_per_round=1.0,
                   kernel_bytes_per_round=1.0, peaks=PEAKS)
    with pytest.raises(ValueError, match="spans"):
        _reading(tr.Trace(t.devices, [], t.host))


def test_op_name_of_a_tpu_event():
    raw = ("%fedgia_update_batched_kernel_donated.9 = (f32[1000000,1,128]"
           "{2,1,0:T(1,128)}) custom-call(s32[1000448]{0:T(1024)S(1)} "
           "%copy-done), custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(raw) == "fedgia_update_batched_kernel_donated.9"
    assert tr.op_name("fusion.1") == "fusion.1"
    assert tr.CONTAINER.match("while.5") and tr.CONTAINER.match("conditional")
    assert not tr.CONTAINER.match("while_fusion.2")


def test_a_trace_recorded_on_a_v5e():
    # one 16-round call of xdev_1m.rounds (10^6 clients, n = 100): the
    # device's ops by their HLO names, the benchmark's span, the host's
    # events in it, and some raw event names as the trace gives them
    with gzip.open(os.path.join(HERE, "traces", "xdev_1m.rounds.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    for raw in rec["raw_names"]:
        assert raw.startswith("%") and " = " in raw
    trace = tr.Trace({int(k): [tuple(e) for e in v]
                      for k, v in rec["devices"].items()},
                     [tuple(e) for e in rec["spans"]],
                     [tuple(e) for e in rec["host"]])
    spec = run.Spec()
    cell = spec.cell("xdev_1m.rounds")
    cfg = spec.config(cell)
    from bench import counting

    r = tr.Reading(trace, chips=1, rounds=16, calls=1, peaks=PEAKS,
                   kernels=cfg["kernels"], **counting.for_config(cfg))
    assert 0 < r.busy_s <= r.window_s
    # the kernel's 10^6 grid steps take most of the round
    assert 0.8 * r.busy_s < r.kernel_s("fedgia_update") < r.busy_s
    got = tr.collect(r, {m["name"]: spec.reader(m["name"])
                         for m in spec.metrics("per_layer", cell)})
    assert set(got) == {m["name"] for m in spec.metrics("per_layer", cell)}
    assert 0 < got["fedgia_update_roofline"] < 100
    assert 0 < got["round_mfu"] < 100
    assert 0 <= got["device_idle_share.rounds"] < 100
    assert got["fedgia_update_ms"] > got["xla_ops_ms"] > 0
    ops = [n for n, _ in r.breakdown()["device_ops"]]
    assert ops[0].startswith("fedgia_update_batched_kernel")
    assert not any(tr.CONTAINER.match(n) for n in ops)


def test_a_four_chip_trace_recorded_on_a_v5e():
    # one 16-round call of xdev_8m.shard4 (8*10^6 clients over 4 chips):
    # eq. (11)'s all-reduce is named for its primitive (`psum.44 = ...
    # all-reduce(...)`), as are the diagonal-H max (`pmax`) and the
    # metrics' scalars (`all-reduce`)
    with gzip.open(os.path.join(HERE, "traces", "xdev_8m.shard4.json.gz"),
                   "rt") as f:
        rec = json.load(f)
    assert any(" all-reduce(" in raw for raw in rec["raw_names"])
    trace = tr.Trace({int(k): [tuple(e) for e in v]
                      for k, v in rec["devices"].items()},
                     [tuple(e) for e in rec["spans"]],
                     [tuple(e) for e in rec["host"]])
    spec = run.Spec()
    cell = spec.cell("xdev_8m.shard4")
    cfg = spec.config(cell)
    from bench import counting

    r = tr.Reading(trace, chips=4, rounds=16, calls=1, peaks=PEAKS,
                   kernels=cfg["kernels"], collectives=cfg["collectives"],
                   **counting.for_config(cfg))
    pattern = re.compile(cfg["collectives"])
    named = {n for ops in r.chip_ops for n, _, _ in ops if pattern.search(n)}
    assert {n.split(".")[0] for n in named} == {"psum", "pmax", "all-reduce"}
    got = tr.collect(r, {m["name"]: spec.reader(m["name"])
                         for m in spec.metrics("per_layer", cell)})
    assert set(got) == {m["name"] for m in spec.metrics("per_layer", cell)}
    assert 0 < got["collective_ms"] < got["fedgia_update_ms"]
    assert 0 < got["fedgia_update_roofline"] < 100
    assert 0 < got["round_mfu"] < 100
    assert got["xla_ops_ms"] > got["fedgia_update_ms"] > 0
    busy_ms = 1e3 * r.busy_s / 16
    assert got["xla_ops_ms"] + got["fedgia_update_ms"] + \
        got["collective_ms"] == pytest.approx(busy_ms, rel=1e-6)
