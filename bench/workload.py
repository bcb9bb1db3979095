"""The system under test, built from a configuration file and a seed, and
driven by a traffic file.

The program is entered where its users enter it: `core.make_algorithm`
and `algo.init` build the algorithm and its state (what
`launch.train.build_algorithm` does, with the benchmark's own copy of
the data generator), and every timed call is one `core.run_rounds`.
Everything that differs between configurations is named in the
configuration file and found by that name: the data generator
(`bench/generators/<data>.py`), the model (`model.class` in
`repro.models`, or `model.arch`, a registered architecture of
`repro.configs` with `model.overrides`, as a `repro.models.Transformer`),
the problem's plain terms (`bench/losses/<problem>.py`), every
`FedConfig` field the file gives, the keyword arguments of `run_rounds`
(`run_rounds`), and the algorithm's plain reference
(`bench/references/<algorithm>.py`, without which the algorithm is
refused).

A cell on more than one chip lays its clients by rows over a `data`
mesh of its chips, as `launch.train --shard-clients` does: the batch
goes from the host straight to its row shards, `algo.init` writes the
state into them, and every call passes the mesh to `run_rounds`.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from bench import reference as ref

TRAFFIC_MODES = ("rounds", "solve")
CLIENT_AXIS = "data"


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
    return ref.load("generators", cfg["data"]).make(
        cfg, cfg.get("data_seed", seeds(seed)["data"]))


def layout(chips: int):
    """A `data` mesh over the cell's chips, or None on one chip."""
    if chips <= 1:
        return None
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh(data=chips)


def placement(mesh, leading: Optional[str], ndim: int) -> NamedSharding:
    """`leading` over the first axis and nothing else, spelt as the
    engine's `shard_inputs` spells it, so that it finds nothing to move."""
    spec = PartitionSpec(leading, *([None] * (ndim - 1))) if ndim else \
        PartitionSpec()
    return NamedSharding(mesh, spec)


def start(cfg: dict, seed: int) -> Optional[np.ndarray]:
    """x⁰ (n,), flat, from the problem's `init(cfg, seed)` where it gives
    one; None for the paper's x⁰ = 0."""
    init = getattr(ref.load("losses", cfg["problem"]), "init", None)
    return None if init is None else \
        np.asarray(init(cfg, seeds(seed)["init"]), np.float32)


def reference(cfg: dict):
    """The plain reference of the configuration's algorithm."""
    return ref.load("references", cfg["algorithm"])


def reference_outputs(cfg: dict, data: dict, seed: int, rounds: int,
                      precision: str = "highest", mesh=None) -> dict:
    """The reference over the first `rounds` rounds of the seed's run
    (`precision="high"`: the control), over `mesh` where the cell has
    one."""
    return reference(cfg).run(cfg, data, rounds, selection(cfg, seed),
                              precision, mesh=mesh, x0=start(cfg, seed))


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
    return float(np.float32(ref.lipschitz(cfg, data)))


def make_model(cfg: dict):
    """The configuration's model; its raveled parameters must number
    `dim`, the width every flat buffer and the reference use."""
    import repro.models

    args = dict(cfg["model"])
    if "arch" in args:
        from repro.configs import get_config

        arch = dataclasses.replace(get_config(args["arch"]),
                                   **args.get("overrides", {}))
        model = repro.models.Transformer(arch)
    else:
        model = getattr(repro.models, args.pop("class"))(cfg["dim"], **args)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    size = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    if size != cfg["dim"]:
        raise ValueError(f"configuration {cfg['name']!r} gives dim "
                         f"{cfg['dim']}, its model has {size} parameters")
    return model


def params_from(shapes, x0: np.ndarray):
    """The flat x⁰ as parameters shaped as `shapes`, leaf by leaf in the
    order of the pytree's leaves."""
    leaves, treedef = jax.tree.flatten(shapes)
    ends = np.cumsum([int(np.prod(l.shape)) for l in leaves])
    parts = np.split(x0, ends[:-1])
    return treedef.unflatten([jnp.asarray(p.reshape(l.shape), l.dtype)
                              for p, l in zip(parts, leaves)])


@dataclasses.dataclass
class Problem:
    algo: Any
    state0: Any
    batch: Any
    policy: Any
    cfg: dict
    mesh: Any = None


def build(cfg: dict, data: dict, seed: int, mesh=None) -> Problem:
    from repro.config import FedConfig
    from repro.core import make_algorithm, make_policy

    reference(cfg)  # an algorithm with no reference cannot be checked
    model = make_model(cfg)
    fields = {f.name for f in dataclasses.fields(FedConfig)}
    fed_args = {k: v for k, v in cfg.items() if k in fields}
    given_r = cfg.get("lipschitz_given", False)
    if given_r:
        fed_args["lipschitz"] = lipschitz_bound(cfg, data)
    fed = FedConfig(**fed_args)
    algo = make_algorithm(fed, model.loss, model=model)
    s = seeds(seed)
    key = jax.random.PRNGKey(s["init"])
    x0 = start(cfg, seed)
    params0 = model.init(key) if x0 is None else \
        params_from(jax.eval_shape(model.init, key), x0)
    if mesh is None:
        batch = {k: jnp.asarray(v) for k, v in data.items()}
        state0 = algo.init(params0, key,
                           init_batch=None if given_r else batch)
    else:
        if not given_r:
            raise ValueError("a cell over several chips needs "
                             "`lipschitz_given`: algo.init over a sharded "
                             "batch gathers all of it to every chip")
        batch = {k: jax.device_put(v, placement(mesh, CLIENT_AXIS, v.ndim))
                 for k, v in data.items()}
        init = lambda p, k: algo.init(p, k, init_batch=None)
        client = set(algo.client_state_keys)
        out = {k: jax.tree.map(lambda l, k=k: placement(
                   mesh, CLIENT_AXIS if k in client else None, l.ndim), v)
               for k, v in jax.eval_shape(init, params0, key).items()}
        state0 = jax.jit(init, out_shardings=out)(params0, key)
    policy = None
    if cfg["participation"] == "uniform":
        policy = make_policy("uniform", cfg["num_clients"], cfg["alpha"],
                             seed=s["participation"])
    jax.block_until_ready(state0)
    return Problem(algo, state0, batch, policy, cfg, mesh)


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
                         mesh=p.mesh, **p.cfg.get("run_rounds", {}))
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
