"""A cell on four chips, at a test's size, on four CPU devices: the whole
harness lays xdev_8m.shard4's clients by rows over a `data` mesh, reads
`correct` on the sound program, and reads it false with the timed path
broken underneath; `run_rounds` finds state and batch where they belong
and moves none of them.

The devices are made per subprocess (`conftest.fake_device_env`): the
flag must be set before JAX is imported.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tests"))

from conftest import fake_device_env  # noqa: E402

SCRIPT = textwrap.dedent("""
    import json
    import jax
    import jax.numpy as jnp
    from bench import run, workload
    from repro.core import api, engine
    from repro.core.fedgia import FedGiA

    SMALL = dict(num_clients=4096, samples=4096, alpha=0.01)

    class SmallSpec(run.Spec):
        def config(self, cell):
            cfg = super().config(cell)
            cfg.update(SMALL)
            return cfg

    spec = SmallSpec()
    cell = spec.cell("xdev_8m.shard4")
    out = {}

    def one_run():
        return run.run_cell(spec, cell, 2**31 + 11, 0.0, False,
                            jax.devices()[:4], {}, 0.0,
                            peak_fn=lambda devs: 1)

    # where run_rounds finds the state and the batch, and what it moves
    moved, placed = [], []
    orig_shard = engine.shard_inputs

    def spy(algo, state, batch, mesh, client_axis="data"):
        s2, b2 = orig_shard(algo, state, batch, mesh, client_axis)
        client = set(algo.client_state_keys)
        for tree, new, rows in ((state, s2, client), (batch, b2, None)):
            for key in tree:
                by_rows = rows is None or key in rows
                for a, b in zip(jax.tree.leaves(tree[key]),
                                jax.tree.leaves(new[key])):
                    same = [x.data.unsafe_buffer_pointer()
                            == y.data.unsafe_buffer_pointer()
                            for x, y in zip(a.addressable_shards,
                                            b.addressable_shards)]
                    equivalent = a.sharding.is_equivalent_to(b.sharding,
                                                             a.ndim)
                    if not equivalent or (by_rows and not all(same)):
                        moved.append(key)
                    if by_rows:
                        placed.append([key, len(a.sharding.device_set),
                                       a.addressable_shards[0].data.shape[0],
                                       a.shape[0]])
        return s2, b2

    engine.shard_inputs = spy
    cfg = spec.config(cell)
    problem = workload.build(cfg, workload.make_data(cfg, 3), 3,
                             workload.layout(cell["chips"]))
    out["state0"] = sorted(
        [k, str(l.sharding.spec), len(l.sharding.device_set)]
        for k in problem.algo.client_state_keys if k in problem.state0
        for l in jax.tree.leaves(problem.state0[k]))
    out["batch"] = sorted([k, str(v.sharding.spec)]
                          for k, v in problem.batch.items())
    res = workload.Caller(problem, spec.traffic(cell)).call(problem.state0)
    out["after"] = sorted(str(l.sharding.spec)
                          for k in ("z", "pi", "h")
                          for l in jax.tree.leaves(res.state[k]))
    del problem, res
    out["sound"] = one_run()
    out["moved"], out["placed"] = moved, placed
    engine.shard_inputs = orig_shard

    def answer_altered(orig):
        def broken(self, state, *a, **kw):
            new, metrics = orig(self, state, *a, **kw)
            z = new["z"]
            # row 0 of the first shard only: one client of the 4096
            ax = api.client_axis()
            first = 1.0 if ax is None else jax.lax.axis_index(ax) == 0
            bump = first * 1e-3 * (jnp.linalg.norm(z[0]) + 1e-3)
            return dict(new, z=z.at[0, 0].add(bump)), metrics
        return broken

    orig_round = FedGiA.round_flat
    FedGiA.round_flat = answer_altered(orig_round)
    out["answer_altered"] = one_run()
    FedGiA.round_flat = orig_round

    orig_mean = api.client_mean

    def half_mean(tree, axis=0, mask=None, weights=None):
        half = jax.tree.map(lambda x: x[: x.shape[0] // 2], tree)
        m = None if mask is None else mask[: mask.shape[0] // 2]
        return orig_mean(half, axis=axis, mask=m, weights=weights)

    api.client_mean = half_mean
    out["half_mean"] = one_run()
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs():
    env = fake_device_env(4)
    env["PYTHONPATH"] += os.pathsep + ROOT
    p = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    lines = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-4000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_state_and_batch_lie_by_rows_over_four_chips(runs):
    assert runs["state0"] and all(spec == "PartitionSpec('data', None)"
                                  and devices == 4
                                  for _, spec, devices in runs["state0"])
    assert {k for k, _ in runs["batch"]} == {"A", "b", "mask"}
    assert all(spec.startswith("PartitionSpec('data'")
               for _, spec in runs["batch"])
    assert all(s == "PartitionSpec('data', None)" for s in runs["after"])


def test_run_rounds_moves_none_of_them(runs):
    assert runs["placed"]
    assert all(devices == 4 and rows * 4 == m
               for _, devices, rows, m in runs["placed"])
    assert runs["moved"] == []


def test_sound_program_is_correct_over_four_chips(runs):
    res = runs["sound"]
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["answer_altered", "half_mean"])
def test_broken_timed_path_is_not_correct_over_four_chips(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]
