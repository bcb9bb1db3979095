"""The system under test, built from a configuration file and a seed, and
driven by a traffic file.

The program is entered where its users enter it: `core.make_algorithm`
and `algo.init` build the algorithm and its state (what
`launch.train.build_algorithm` does, with the benchmark's own copy of
the data generator), and every timed call is one `core.run_rounds`.
Everything that differs between configurations is named in the
configuration file and found by that name: the data generator
(`bench/generators/<data>.py`), the model (`model.class` in
`repro.models`), every `FedConfig` field the file gives, the keyword
arguments of `run_rounds` (`run_rounds`), and the algorithm's plain
reference (`bench/references/<algorithm>.py`, without which the
algorithm is refused).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import lipschitz, load

TRAFFIC_MODES = ("rounds", "solve")


def seeds(seed: int) -> dict:
    """Independent 31-bit seeds for the data, the init key and the
    participation draw, all from one `--seed` of any size."""
    data, init, part = np.random.SeedSequence(seed % 2**64).generate_state(3)
    mask = 0x7FFFFFFF
    return {"data": int(data) & mask, "init": int(init) & mask,
            "participation": int(part) & mask}


def make_data(cfg: dict, seed: int) -> dict:
    """The cell's data, on the host: from the seed, or from the
    configuration's own `data_seed` where it names one fixed dataset."""
    return load("generators", cfg["data"]).make(
        cfg, cfg.get("data_seed", seeds(seed)["data"]))


def reference(cfg: dict):
    """The plain reference of the configuration's algorithm."""
    return load("references", cfg["algorithm"])


def reference_outputs(cfg: dict, data: dict, seed: int, rounds: int,
                      precision: str = "highest") -> dict:
    """The reference over the first `rounds` rounds of the seed's run
    (`precision="high"`: the control)."""
    return reference(cfg).run(cfg, data, rounds, selection(cfg, seed),
                              precision)


def selection(cfg: dict, seed: int) -> dict:
    """How the round's participants are drawn, for the reference."""
    m = cfg["num_clients"]
    n_sel = max(1, min(m, int(round(cfg["alpha"] * m))))
    s = seeds(seed)
    if cfg["participation"] == "uniform":
        return {"kind": "uniform", "seed": s["participation"],
                "n_selected": n_sel}
    return {"kind": "internal", "seed": s["init"], "n_selected": n_sel}


def lipschitz_bound(cfg: dict, data: dict) -> float:
    """r worked out on the host in float64, for a configuration that gives
    it to the program (`lipschitz_given`) instead of having `algo.init`
    take a spectral norm per client: with one sample per client each of
    those is an SVD that r_i = ||a_i||^2 / d_i makes needless, and at
    10^6 clients they took most of the set-up on a v5e."""
    return float(np.float32(lipschitz(cfg, data)))


@dataclasses.dataclass
class Problem:
    algo: Any
    state0: Any
    batch: Any
    policy: Any
    cfg: dict


def build(cfg: dict, data: dict, seed: int) -> Problem:
    import repro.models
    from repro.config import FedConfig
    from repro.core import make_algorithm, make_policy

    reference(cfg)  # an algorithm with no reference cannot be checked
    model_args = dict(cfg["model"])
    model = getattr(repro.models, model_args.pop("class"))(cfg["dim"],
                                                          **model_args)
    fields = {f.name for f in dataclasses.fields(FedConfig)}
    fed_args = {k: v for k, v in cfg.items() if k in fields}
    given_r = cfg.get("lipschitz_given", False)
    if given_r:
        fed_args["lipschitz"] = lipschitz_bound(cfg, data)
    fed = FedConfig(**fed_args)
    algo = make_algorithm(fed, model.loss, model=model)
    batch = {k: jnp.asarray(v) for k, v in data.items()}
    s = seeds(seed)
    key = jax.random.PRNGKey(s["init"])
    state0 = algo.init(model.init(key), key,
                       init_batch=None if given_r else batch)
    policy = None
    if cfg["participation"] == "uniform":
        policy = make_policy("uniform", cfg["num_clients"], cfg["alpha"],
                             seed=s["participation"])
    jax.block_until_ready(state0)
    return Problem(algo, state0, batch, policy, cfg)


class Caller:
    """One traffic mix over one problem: `call(state)` is one timed
    `run_rounds` call.

    mode "rounds": `rounds_per_call` rounds in chunks of `chunk_size`, no
    stopping rule; the window chains each call's state into the next.
    mode "solve": from the initial state to the eq. (35) stop (or
    `max_rounds`), chunks of `chunk_size` with one host sync each; every
    call starts again from the initial state."""

    def __init__(self, problem: Problem, traffic: dict):
        if traffic["mode"] not in TRAFFIC_MODES:
            raise ValueError(f"traffic mode {traffic['mode']!r} not in "
                             f"{TRAFFIC_MODES}")
        self.p = problem
        self.t = traffic

    @property
    def chains(self) -> bool:
        return self.t["mode"] == "rounds"

    def call(self, state):
        from repro.core import run_rounds

        p, t = self.p, self.t
        if t["mode"] == "rounds":
            num, tol = t["rounds_per_call"], 0.0
        else:
            num, tol = t["max_rounds"], p.cfg["tol"]
        res = run_rounds(p.algo, state, p.batch, num, tol=tol,
                         chunk_size=t["chunk_size"], participation=p.policy,
                         **p.cfg.get("run_rounds", {}))
        jax.block_until_ready(res.state)
        return res

    def solved(self, res) -> bool:
        """A solve that never met the stopping rule has failed."""
        return self.t["mode"] != "solve" or bool(res.stopped_early)


def host_outputs(cfg: dict, res) -> dict:
    """What a call produced, on the host, in the reference's names."""
    from bench.compare import HISTORY

    out = {k: np.asarray(res.history[k]) for k in HISTORY}
    state = reference(cfg).program_state(res.state)
    out.update({k: np.asarray(jax.device_get(v)) for k, v in state.items()})
    out["rounds_run"] = int(res.rounds_run)
    out["stopped_early"] = bool(res.stopped_early)
    return out


def free(*objs: Optional[Any]) -> None:
    """Delete the device buffers of the given pytrees now."""
    for o in objs:
        for leaf in jax.tree.leaves(o):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()
