"""The comparison that decides `correct`, at sizes a test run holds.

- the control, the reference itself in three bf16 passes in the
  program's place, fails each cell's limits;
- a run of the whole harness (the look for a chip skipped) is correct on
  the sound program and not correct with the timed path broken
  underneath: a round that returns its state unchanged, eq. (11)'s mean
  taken over half the clients, and one client's answer altered where the
  round produces it.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, run, workload

SMALL = {
    "fedgia_xdev_1m": dict(num_clients=2048, samples=2048, alpha=0.01),
    "fedgia_paper_v1": dict(num_clients=16, dim=64, samples=800),
    "fedgia_xdev_8m": dict(num_clients=4096, samples=4096, alpha=0.01),
}
CELLS = ("xdev_1m.rounds", "paper_v1.solve")  # one chip: whole runs here
ALL_CELLS = CELLS + ("xdev_8m.shard4",)  # four chips: test_bench_mesh.py
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


class SmallSpec(run.Spec):
    """The committed cells at sizes a CPU test holds."""

    def config(self, cell):
        cfg = super().config(cell)
        cfg.update(SMALL[cfg["name"]])
        return cfg


@pytest.fixture(scope="module")
def spec():
    return SmallSpec()


def one_run(spec, name, seed=2**31 + 11):
    return run.run_cell(spec, spec.cell(name), seed, 0.0, False,
                        jax.devices()[:1], PEAKS, 0.0,
                        peak_fn=lambda devs: 1)


@pytest.mark.parametrize("name", ALL_CELLS)
def test_control_fails_the_limits(spec, name):
    cell = spec.cell(name)
    cfg, limits = spec.config(cell), spec.limits(cell)
    seed = 2**31 + 5
    data = workload.make_data(cfg, seed)
    rounds = 16
    ref = workload.reference_outputs(cfg, data, seed, rounds)
    ctl = workload.reference_outputs(cfg, data, seed, rounds, "high")
    assert compare.verdict(compare.numbers(ref, ref, limits["grad_floor"]),
                           limits)
    assert not compare.verdict(
        compare.numbers(ctl, ref, limits["grad_floor"]), limits)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(spec, name):
    res = one_run(spec, name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {
        m["name"] for m in spec.metrics("end_to_end", spec.cell(name))}


def _state_unchanged(orig):
    def broken(self, state, *a, **kw):
        _, metrics = orig(self, state, *a, **kw)
        return state, metrics
    return broken


def _answer_altered(orig):
    def broken(self, state, *a, **kw):
        new, metrics = orig(self, state, *a, **kw)
        z = new["z"]
        bump = 1e-3 * (jnp.linalg.norm(z[0]) + 1e-3)
        return dict(new, z=z.at[0, 0].add(bump)), metrics
    return broken


def _half_mean(orig_mean):
    def broken(tree, axis=0, mask=None, weights=None):
        half = jax.tree.map(lambda x: x[: x.shape[0] // 2], tree)
        m = None if mask is None else mask[: mask.shape[0] // 2]
        return orig_mean(half, axis=axis, mask=m, weights=weights)
    return broken


@pytest.mark.parametrize("fault", ["state_unchanged", "half_mean",
                                   "answer_altered"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(spec, name, fault, monkeypatch):
    from repro.core import api
    from repro.core.fedgia import FedGiA

    if fault == "half_mean":
        monkeypatch.setattr(api, "client_mean", _half_mean(api.client_mean))
    else:
        wrap = {"state_unchanged": _state_unchanged,
                "answer_altered": _answer_altered}[fault]
        monkeypatch.setattr(FedGiA, "round_flat", wrap(FedGiA.round_flat))
    res = one_run(spec, name)
    assert not res["correct"], res["checks"]


def test_limits_files_name_every_number():
    here = os.path.dirname(os.path.abspath(__file__))
    for name in ALL_CELLS:
        with open(os.path.join(here, "limits", name + ".json")) as f:
            lim = json.load(f)
        assert lim["selected_gap"] == 0
        assert all(np.isfinite(lim[k]) for k in compare.compared(lim))


def test_an_algorithm_without_a_reference_is_refused(spec):
    cfg = spec.config(spec.cell("paper_v1.solve"))
    cfg["algorithm"] = "fedpd"
    data = workload.make_data(cfg, 3)
    with pytest.raises(ValueError, match="bench/references/fedpd.py"):
        workload.build(cfg, data, 3)


def test_the_configuration_names_what_is_built(spec):
    from repro.core.fedgia import FedGiA

    cfg = spec.config(spec.cell("paper_v1.solve"))
    problem = workload.build(cfg, workload.make_data(cfg, 3), 3)
    assert isinstance(problem.algo, FedGiA)
    assert problem.algo.fed.num_clients == cfg["num_clients"]
    assert problem.algo.fed.h_policy == cfg["h_policy"]
    assert problem.policy is None and cfg["participation"] == "internal"


def test_the_control_keeps_its_low_parts_under_jit():
    from bench import reference

    a = jnp.asarray(np.linspace(0.1, 3.0, 257, dtype=np.float32))
    hi, lo = jax.jit(reference._split)(a)
    assert np.any(np.asarray(lo) != 0)
    np.testing.assert_array_equal(np.asarray(hi + lo),
                                  np.asarray(reference._split(a)[0]
                                             + reference._split(a)[1]))
    s = jnp.asarray(np.linspace(-2.0, 2.0, 5, dtype=np.float32))
    rows = jnp.asarray(np.arange(5 * 257, dtype=np.float32).reshape(5, 257)
                       / 7.0)
    np.testing.assert_array_equal(
        np.asarray(reference.scale_rows(rows, s, "highest")),
        np.asarray(rows) * np.asarray(s)[:, None])
    high = np.asarray(reference.scale_rows(rows, s, "high"), np.float64)
    exact = np.asarray(rows, np.float64) * np.asarray(s, np.float64)[:, None]
    gap = np.max(np.abs(high - exact) / np.maximum(np.abs(exact), 1e-30))
    assert 0 < gap < 2.0 ** -14


def test_a_given_lipschitz_bound_is_the_largest_row_norm():
    from bench.generators import million_client_batch

    cfg = {"num_clients": 300, "dim": 100, "problem": "linreg"}
    data = million_client_batch.million_client_batch(300, 100, 11)
    a = np.asarray(data["A"][:, 0, :], np.float64)
    want = np.float32(np.max(np.sum(a * a, axis=1)))
    assert workload.lipschitz_bound(cfg, data) == pytest.approx(float(want))


def test_row_blocks_read_what_the_whole_arrays_read():
    from bench import reference

    rng = np.random.default_rng(4)
    m = 3 * reference.ROW_BLOCK + 5
    ref = rng.standard_normal((m, 7)).astype(np.float32)
    prog = ref + 1e-5 * rng.standard_normal((m, 7)).astype(np.float32)
    p64, r64 = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    diff = np.linalg.norm(p64 - r64, axis=1)
    norm = np.linalg.norm(r64, axis=1)
    want = np.max(diff / np.maximum(norm, np.median(norm)))
    assert compare.row_gap(prog, ref) == want
    data = {"A": ref[:, None, :], "mask": np.ones((m, 1), np.float32)}
    top = np.sum(r64 ** 2, axis=1)
    assert reference.lipschitz({"problem": "linreg"}, data) == np.max(top)
