"""mellum2.silo4's configuration at a test's size, on the CPU: the program
(Mellum2's layer pattern, banded attention, the expert share) through
`run_rounds`, against the plain model reference `bench/losses/mellum2.py`
driven by `bench/references/fedgia.py`, exactly as a run on the chip
compares them; at this size the program computes in float32.

Seeded random weights: hidden 64, 4 query heads over 2 KV heads of 16,
8 experts of which 2 are held (ids 2 and 3), top-2... as the catalog's
keys say below, a window of 8 keys, sequences of 32 tokens, 4 silos.
The learning rate is 0.1 here (the cell's is 3e-5): FedGiA's reference
computes each ADMM step as x = x̄ - D(ḡ + π) and then x - x̄, which in
float32 loses |x̄|·2⁻²⁴ against a step of lr·|g|; at 3e-5 that loss reads
up to 10⁻² in π's rows, at 0.1 about 10⁻⁵, below the limits here.
"""
import gzip
import json
import os

import jax
import numpy as np
import pytest

from bench import compare, counting, device, run, workload
from bench import trace as tr
from bench.losses import mellum2

CELL = "mellum2.silo4"
MODEL = dict(num_layers=4, d_model=64, num_heads=4, num_kv_heads=2,
             head_dim=16, moe_d_ff=32, num_experts=8, experts_held=2,
             first_expert=2, sliding_window=8, vocab_size=64, attn_block=8,
             dtype="float32")
CATALOG = {"hidden_size": 64, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "moe_intermediate_size": 32, "router_experts": 8,
           "num_experts": 2, "first_expert": 2, "num_experts_per_tok": 2,
           "sliding_window": 8, "vocab_size": 64, "seq_len": 32,
           "sequences": 2, "lipschitz": 1.0 / (0.15 * 0.1)}
MODEL["experts_per_token"] = CATALOG["num_experts_per_tok"]
TRAFFIC = {"mode": "rounds", "rounds_per_call": 3, "chunk_size": 3,
           "trace_calls": 1}
# float32 on both sides. loss_gap: the per-round mean loss agrees to sums
# taken in another order (~1e-7 read). state_gap: pi's rows carry each
# silo's gradient, whose sums over 64 tokens and 2 held experts reorder
# (~1e-6), and the reference's ADMM steps lose ~1e-5 (module docstring).
# grad_gap: the engine's ||grad f||^2 is one float32 sum of n squares,
# which XLA:CPU accumulates in a long sequential order once it is fused
# into the jitted round (3.4e-4 read at this size; 1e-7 eagerly); the
# reference's is a dot.
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-3, "state_gap": 1e-4,
          "selected_gap": 0, "grad_floor": 0.0}


def full_config() -> dict:
    """bench/configs/fedgia_mellum2.json, the configuration of the cell."""
    with open(os.path.join(os.path.dirname(__file__), "configs",
                           "fedgia_mellum2.json")) as f:
        return json.load(f)


class SmallSpec(run.Spec):
    """The cell at a test's size, on `chips` devices."""

    chips = 1

    def cell(self, name):
        return {"name": CELL, "config": "fedgia_mellum2",
                "traffic": "silo_c4", "chips": self.chips}

    def config(self, cell):
        cfg = full_config()
        cfg.update(CATALOG, chips=self.chips)
        cfg["model"] = {"arch": cfg["model"]["arch"],
                        "overrides": dict(MODEL)}
        cfg["dim"] = mellum2.size(cfg)
        return cfg

    def traffic(self, cell):
        return dict(TRAFFIC)

    def limits(self, cell):
        return dict(LIMITS)


def one_run(spec, seed=2**31 + 7):
    cell = spec.cell(CELL)
    return run.run_cell(spec, cell, seed, 0.0, False,
                        jax.devices()[:cell["chips"]], {}, 0.0,
                        peak_fn=lambda devs: 1)


def test_the_reference_leaves_are_the_programs():
    """bench/losses/mellum2.py lays x out as the program's pytree is laid
    out, leaf for leaf, at the cell's own sizes."""
    cfg = full_config()
    model = workload.make_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = [tuple(l.shape) for l in jax.tree.leaves(shapes)]
    assert got == [shape for _, shape, _ in mellum2.leaves(cfg)]
    assert mellum2.size(cfg) == cfg["dim"]


def test_the_program_is_correct_on_one_device():
    res = one_run(SmallSpec())
    assert res["correct"], res["checks"]
    assert res["window"]["checked_call_rounds"] == TRAFFIC["rounds_per_call"]


def test_the_control_is_not_correct():
    """The control (every product's operands at 3 bits of mantissa) fails
    the limits the program passes."""
    spec = SmallSpec()
    cfg = spec.config(spec.cell(CELL))
    data = workload.make_data(cfg, 3)
    ref = workload.reference_outputs(cfg, data, 3, 2)
    ctl = workload.reference_outputs(cfg, data, 3, 2, "high")
    assert not compare.verdict(compare.numbers(ctl, ref, 0.0), LIMITS)


def test_counts_count_the_band_not_the_square():
    cfg = full_config()
    S, W = cfg["seq_len"], cfg["sliding_window"]
    wide = dict(cfg, sliding_window=S)
    per_layer = 4 * cfg["num_attention_heads"] * cfg["head_dim"]
    saved = (mellum2.forward_flops_per_token(wide, S)
             - mellum2.forward_flops_per_token(cfg, S))
    # three window layers: (S + 1)/2 keys a query at full width, less
    # the band's mean, W - W (W - 1) / (2 S)
    band = (W * (W + 1) / 2 + (S - W) * W) / S
    assert saved == pytest.approx(3 * per_layer * ((S + 1) / 2 - band))
    counts = mellum2.counts(cfg)
    assert counts["kernel_bytes_per_round"] == 7 * 4 * cfg["dim"] * 4 + 24


def test_the_cell_is_declared_as_its_files_say():
    """BENCHMARK.json's mellum2.silo4 and the files it is found by: one
    silo a chip, 4-round calls chained, every key the configuration
    cuts listed in `reduced`, and limits on all four numbers."""
    spec = run.Spec()
    cell = spec.cell(CELL)
    cfg = spec.config(cell)
    assert cell["chips"] == cfg["chips"] == cfg["num_clients"] == 4
    traffic = spec.traffic(cell)
    assert (traffic["mode"], traffic["rounds_per_call"],
            traffic["chunk_size"], traffic["trace_calls"]) == \
        ("rounds", 4, 4, 2)
    entry = next(c for c in spec.bench["configs"]
                 if c["name"] == cell["config"])
    assert set(entry["reduced"]) == set(cfg["cut"])
    assert set(spec.limits(cell)) == set(compare.NUMBERS) | {"grad_floor"}
    names = {m["name"] for m in spec.metrics("per_layer", cell)}
    assert {"moe_experts_ms", "collective_ms", "fedgia_update_roofline",
            "round_mfu"} <= names


def test_moe_experts_ms_reads_nothing_where_no_expert_kernel_runs():
    """Other cells name no `moe_experts` kernel: the reader leaves its
    metric out of their lines."""
    reader = run.Spec().reader("moe_experts_ms")

    class NoExperts:
        rounds = 4

        def kernel_s(self, name):
            return None

    assert reader.read(NoExperts()) is None


def test_a_four_chip_mellum2_trace_recorded_on_a_v5e():
    # one 4-round call of mellum2.silo4 on four v5e (host events of
    # 0.1 ms and more): the held experts' products are ops of their own
    # (`ragged-dot-*`), so moe_experts_ms reads them; eq. (11) is a psum
    with gzip.open(os.path.join(os.path.dirname(__file__), "traces",
                                CELL + ".json.gz"), "rt") as f:
        rec = json.load(f)
    trace = tr.Trace({int(k): [tuple(e) for e in v]
                      for k, v in rec["devices"].items()},
                     [tuple(e) for e in rec["spans"]],
                     [tuple(e) for e in rec["host"]])
    spec = run.Spec()
    cell = spec.cell(CELL)
    cfg = spec.config(cell)
    r = tr.Reading(trace, chips=4, rounds=4, calls=1,
                   peaks=device.peaks_for("TPU v5 lite"),
                   kernels=cfg["kernels"], collectives=cfg["collectives"],
                   **counting.for_config(cfg))
    got = tr.collect(r, {m["name"]: spec.reader(m["name"])
                         for m in spec.metrics("per_layer", cell)})
    assert set(got) == {m["name"] for m in spec.metrics("per_layer", cell)}
    assert got["moe_experts_ms"] > got["fedgia_update_ms"] > 0
    assert got["xla_ops_ms"] > got["moe_experts_ms"]
    assert 0 < got["collective_ms"] < got["fedgia_update_ms"]
    assert 0 < got["fedgia_update_roofline"] < 100
    assert 0 < got["round_mfu"] < 100
    busy_ms = 1e3 * r.busy_s / 4
    assert got["xla_ops_ms"] + got["fedgia_update_ms"] + \
        got["moe_experts_ms"] + got["collective_ms"] == \
        pytest.approx(busy_ms, rel=1e-6)
