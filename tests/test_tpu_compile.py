"""The main path compiled for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: a kernel operand
that overflows SMEM or VMEM, a block not aligned to the tiling, a
program that does not fit the chip. These tests compile the batched
`fedgia_update` kernel, one flat FedGiA round and the client-sharded
round at real sizes for a `v5e:2x2` topology. Nothing runs.

The TPU compiler also rewrites collectives: with the installed jax 0.9.0
/ libtpu 0.0.34 it emits every reduce-scatter as a full all-reduce
followed by a dynamic slice, at every size tried (512 B to 64 MiB). The
sharded rounds' compiled collectives are pinned exactly, next to
hlo_guard's contract on the lowered program, so that rewrite shows and
any other change to the compiled collectives fails.

The topology is described inside a module fixture: only the worker that
runs these tests loads the TPU library, and where it cannot be loaded
every test here skips.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from hlo_guard import (assert_barrier_round, assert_overlap_round,
                       collective_counts)
from repro.config import FedConfig
from repro.core import engine, make_algorithm
from repro.data import linreg_noniid, logreg_data
from repro.kernels.fedgia_update import (
    fedgia_update_batched_kernel,
    fedgia_update_batched_kernel_donated,
)
from repro.models import LeastSquares, LogisticRegression
from repro.utils import pytree as pt


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU library, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))


@pytest.mark.parametrize("donate", [False, True], ids=["plain", "donated"])
@pytest.mark.parametrize("shape", [(128, 128), (128, 1024), (10**6, 128),
                                   (10**6 + 3, 128), (8, 2**20)],
                         ids=["128x128", "128x1024", "1e6x128", "1e6+3x128",
                              "8x2^20"])
def test_batched_kernel_compiles(one_chip, shape, donate):
    """The blocks `batched_blocks` picks compile: 1024 clients a step at
    (10^6, 128), a ragged last client block at 10^6 + 3, and 2048-lane
    column blocks of a wide cross-silo state. Each client block's
    selects enter VMEM as one (8, 128) int32 tile, which the kernel
    transposes to put clients on sublanes."""
    sds = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt,
                                                          sharding=one_chip)
    args = (sds(shape),) * 4 + (sds(shape[:1], jnp.bool_), sds(()))
    fn = (fedgia_update_batched_kernel_donated if donate
          else fedgia_update_batched_kernel)
    txt = fn.lower(*args, shape[0], k0=5).compile().as_text()
    assert "tpu_custom_call" in txt


def _flat_round(fed, model, raw, place, mesh=None, overlap="off"):
    """Lower one flat round of `fed` on abstract inputs, each placed by
    `place(shape_dtype_struct, partition_spec)`."""
    algo = make_algorithm(fed, model.loss, model=model)
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in raw.items()}
    params = model.init(jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda b: algo.init(params, jax.random.PRNGKey(1), init_batch=b),
        batch)
    spec = pt.ravel_spec(params)
    flat = jax.eval_shape(lambda s: engine.flatten_state(algo, s, spec), state)
    if overlap == "scatter":
        flat["ovl_shard"] = jax.ShapeDtypeStruct((1, spec.padded_size),
                                                 spec.dtype)
    sspec = engine._state_specs(algo, flat, "data")
    bspec = engine._batch_specs(batch, "data")
    args = ({k: jax.tree.map(place, v, sspec[k]) for k, v in flat.items()},
            jax.tree.map(place, batch, bspec))
    rf = engine.make_round_fn(algo, mesh, flat_spec=spec, overlap=overlap,
                              donate_kernel=True)
    return jax.jit(rf, donate_argnums=(0,)).lower(*args)


def test_flat_fedgia_round_holds_kernel(one_chip):
    """The paper's logreg problem (m=128, n=1024): the compiled round runs
    the Pallas kernel, not the jnp twin."""
    fed = FedConfig(algorithm="fedgia", num_clients=128, k0=5, alpha=0.5,
                    sigma_t=0.15, h_policy="diag_ema", use_kernel=True)
    place = lambda a, _: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                              sharding=one_chip)
    lowered = _flat_round(fed, LogisticRegression(1024),
                          logreg_data(0, 12800, 1024, 128), place)
    assert "tpu_custom_call" in lowered.compile().as_text()


def _sharded_linreg_round(mesh4, overlap):
    """The paper's linreg round (m=128, n=100: N=128 lanes) over a
    4-chip `data` mesh; returns (lowered text, compiled text)."""
    fed = FedConfig(algorithm="fedgia", num_clients=128, k0=5, alpha=0.5,
                    sigma_t=0.15, h_policy="diag_ema", use_kernel=True)
    place = lambda a, p: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=NamedSharding(mesh4, p))
    lowered = _flat_round(fed, LeastSquares(100),
                          linreg_noniid(0, 12800, 100, 128), place, mesh4,
                          overlap=overlap)
    return lowered.as_text(dialect="hlo"), lowered.compile().as_text()


def test_sharded_round_one_model_size_all_reduce(mesh4):
    """The client-sharded round over a 4-chip `data` mesh asks for ONE
    model-size all-reduce (eq. (11)) plus the |grad|^2 diagnostic's
    reduce-scatter. The compiler emits that reduce-scatter as a second
    full all-reduce, so on the chip the round's budget of one does NOT
    hold: the compiled program is pinned at exactly two."""
    want, got = _sharded_linreg_round(mesh4, "off")
    assert_barrier_round(want, "lowered sharded round")
    assert "tpu_custom_call" in got
    assert collective_counts(got) == {
        "all-reduce": 2, "reduce-scatter": 0, "all-gather": 0}


def test_sharded_overlap_round_collectives(mesh4):
    """overlap="scatter" asks for one reduce-scatter and one all-gather.
    Compiled at N=128 both become all-reduces: the reduce-scatter as
    everywhere, the all-gather (512 B) as an all-reduce of a zero-padded
    buffer. At N >= 1024 the all-gather stays."""
    want, got = _sharded_linreg_round(mesh4, "scatter")
    assert_overlap_round(want, "lowered overlapped round")
    assert "tpu_custom_call" in got
    assert collective_counts(got) == {
        "all-reduce": 2, "reduce-scatter": 0, "all-gather": 0}


@pytest.mark.parametrize("n", [4 * 4096, 4 * 2**20], ids=["64KiB", "16MiB"])
def test_reduce_scatter_compiles_to_all_reduce(topo, n):
    """The cause of the two pins above, without the engine: a bare
    `psum_scatter` over four v5e chips compiles to a full-size
    all-reduce and a dynamic slice of its result. When a later compiler
    keeps the reduce-scatter, this fails, and the two sharded-round
    tests above should go back to hlo_guard's contract."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    fn = jax.shard_map(
        lambda x: jax.lax.psum_scatter(x[0], "data", tiled=True), mesh=mesh,
        in_specs=P("data", None), out_specs=P("data"), check_vma=False)
    x = jax.ShapeDtypeStruct((4, n), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    lowered = jax.jit(fn).lower(x)
    assert collective_counts(lowered.as_text(dialect="hlo")) == {
        "all-reduce": 0, "reduce-scatter": 1, "all-gather": 0}
    got = lowered.compile().as_text()
    assert collective_counts(got) == {
        "all-reduce": 1, "reduce-scatter": 0, "all-gather": 0}
    assert f"f32[{n}]" in got and "dynamic-slice(%all-reduce" in got


def test_expert_share_compiles_to_named_ragged_dots(one_chip):
    """Mellum2's expert share at its real widths (hidden 2,304, experts of
    896, 8 of 64 held, top-8, 16,384 tokens), forward and gradient, under
    the engine's per-client vmap: the grouped products compile to the
    TPU's ragged-dot kernels, whose names the `moe_experts` pattern of
    bench/configs/fedgia_mellum2.json reads."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import moe

    cfg = dataclasses.replace(get_config("mellum2-12b-a2.5b"),
                              experts_held=8, first_expert=8)
    params = jax.eval_shape(lambda: moe.moe_init(jax.random.PRNGKey(0), cfg,
                                                 jnp.bfloat16))
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip)
    x = place(jax.ShapeDtypeStruct((1, 2, 8192, cfg.d_model), jnp.bfloat16))
    loss = lambda p, x: jnp.sum(
        moe.moe_share_apply(p, cfg, x)[0].astype(jnp.float32))
    grad = jax.vmap(jax.grad(loss), in_axes=(None, 0))
    txt = jax.jit(grad).lower(jax.tree.map(place, params), x).compile().as_text()
    names = re.findall(r"%(ragged-dot[\w.-]*) = ", txt)
    # the backward's six grouped products (each product's input and weight
    # gradients) and the forward's, less what the compiler merges
    assert sum(n.startswith("ragged-dot-none") for n in names) >= 6
