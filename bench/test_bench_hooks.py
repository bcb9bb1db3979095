"""A configuration that names a registered architecture comes as files
alone: the model by `model.arch` and `model.overrides`, the problem's
`loss_grad`, `init` and `counts` from `bench/losses/<problem>.py`. The
harness builds it, makes its `run_rounds` calls and compares them with
the FedGiA reference, with no edit to a file it already has.

The problem module here is the test's own, served where the harness
looks a problem up by name. Its `loss_grad` takes the per-client loss
and gradient from the program's own `Transformer.loss`: it checks the
plumbing from a pytree model to the flat reference, and is not a model
reference. A configuration that adds a model brings a plain one.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import compare, counting, reference, run, workload
from bench.test_bench_correct import _answer_altered

ARCH = "tinyllama-1.1b"
OVERRIDES = dict(num_layers=1, d_model=16, num_heads=2, num_kv_heads=1,
                 head_dim=8, d_ff=32, vocab_size=32, dtype="float32",
                 remat=False)
CLIENTS, BATCH, SEQ = 4, 2, 8
COUNTS = {"flops_per_round": 1234.0, "kernel_bytes_per_round": 5678.0}
LIMITS = {"loss_gap": 1e-4, "state_gap": 1e-4, "selected_gap": 0,
          "grad_floor": 0.0}
TRAFFIC = {"mode": "rounds", "rounds_per_call": 3, "chunk_size": 3,
           "trace_calls": 1}


def _model():
    import dataclasses

    from repro.configs import get_config
    from repro.models import Transformer

    return Transformer(dataclasses.replace(get_config(ARCH), **OVERRIDES))


def _unflat(x):
    model = _model()
    return workload.params_from(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)), x)


def _loss_grad(cfg, data, x, precision):
    model = _model()
    params = _unflat(x)

    def one(tokens):
        f, g = jax.value_and_grad(
            lambda p: model.loss(p, {"tokens": tokens})[0])(params)
        return f, jnp.concatenate([l.reshape(-1) for l in
                                   jax.tree.leaves(g)])
    return jax.vmap(one)(data["tokens"])


def _init(cfg, seed):
    params = _model().init(jax.random.PRNGKey(seed))
    return np.concatenate([np.asarray(l, np.float32).reshape(-1)
                           for l in jax.tree.leaves(params)])


def _tokens(cfg, seed):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(0, OVERRIDES["vocab_size"],
                                   (cfg["num_clients"], BATCH, SEQ + 1),
                                   dtype=np.int32)}


OWN = {
    ("losses", "toy_lm"): types.SimpleNamespace(
        loss_grad=_loss_grad, init=_init, counts=lambda cfg: dict(COUNTS)),
    ("generators", "toy_tokens"): types.SimpleNamespace(make=_tokens),
}


def _dim():
    shapes = jax.eval_shape(_model().init, jax.random.PRNGKey(0))
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))


def _config(dim=None):
    return {
        "name": "toy_lm", "algorithm": "fedgia", "h_policy": "diag_ema",
        "problem": "toy_lm", "data": "toy_tokens",
        "model": {"arch": ARCH, "overrides": dict(OVERRIDES)},
        "num_clients": CLIENTS, "dim": dim or _dim(), "alpha": 0.5,
        "k0": 5, "sigma_t": 30.0, "lipschitz": 4.0,
        "lipschitz_given": True, "participation": "uniform",
        "kernels": {}, "chips": 1,
    }


class HookSpec(run.Spec):
    """One cell of the toy configuration, from this test's files."""

    CELL = {"name": "toy_lm.rounds", "config": "toy_lm",
            "traffic": "toy", "chips": 1, "why": "plumbing"}

    def cell(self, name):
        return dict(self.CELL)

    def config(self, cell):
        return _config()

    def traffic(self, cell):
        return dict(TRAFFIC)

    def limits(self, cell):
        return dict(LIMITS)


@pytest.fixture()
def own_files(monkeypatch):
    orig = reference.load
    monkeypatch.setattr(reference, "load", lambda kind, name: OWN.get(
        (kind, name)) or orig(kind, name))


def _one_run(seed=2**31 + 21):
    spec = HookSpec()
    return run.run_cell(spec, spec.cell("toy_lm.rounds"), seed, 0.0, False,
                        jax.devices()[:1], {}, 0.0, peak_fn=lambda devs: 1)


def test_a_registered_architecture_builds_by_name(own_files):
    from repro.models import Transformer

    cfg = _config()
    problem = workload.build(cfg, workload.make_data(cfg, 5), 5)
    assert isinstance(problem.algo.model, Transformer)
    assert problem.algo.model.cfg.d_model == OVERRIDES["d_model"]
    x = np.concatenate([np.asarray(l).reshape(-1) for l in
                        jax.tree.leaves(problem.state0["x"])])
    np.testing.assert_array_equal(x, _init(cfg, workload.seeds(5)["init"]))


def test_counts_come_from_the_problem_file(own_files):
    assert counting.for_config(_config()) == COUNTS


def test_the_whole_harness_is_correct_on_the_sound_program(own_files):
    res = _one_run()
    assert res["correct"], res["checks"]
    assert res["window"]["checked_call_rounds"] == TRAFFIC["rounds_per_call"]


def test_an_altered_answer_is_not_correct(own_files, monkeypatch):
    from repro.core.fedgia import FedGiA

    monkeypatch.setattr(FedGiA, "round_flat",
                        _answer_altered(FedGiA.round_flat))
    res = _one_run()
    assert not res["correct"], res["checks"]


def test_a_dim_that_is_not_the_models_fails_at_build(own_files):
    cfg = _config(dim=_dim() + 1)
    with pytest.raises(ValueError, match="parameters"):
        workload.build(cfg, workload.make_data(cfg, 5), 5)


def test_the_reference_reads_the_problem_files_gradient(own_files):
    cfg = _config()
    data = reference.device_data(cfg, workload.make_data(cfg, 3))
    x = jnp.asarray(_init(cfg, 3))
    f, g = reference.loss_grad(cfg, data, x, "highest")
    assert f.shape == (CLIENTS,) and g.shape == (CLIENTS, cfg["dim"])
    assert reference.lipschitz(cfg, data) == cfg["lipschitz"]
    nums = compare.numbers(
        *[workload.reference_outputs(cfg, workload.make_data(cfg, 3), 3, 2)
          for _ in range(2)], 0.0)
    assert nums["state_gap"] == 0.0
