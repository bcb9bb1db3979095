"""Substrate tests: data pipeline, optimizers, checkpointing, configs."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.config import INPUT_SHAPES
from repro.configs import ARCHITECTURES, get_config, list_architectures
from repro.data import dirichlet_partition, equal_partition, linreg_noniid
from repro.data.tokens import synthetic_batch_for
from repro.optim import adam, apply_updates, paper_lr, sgd


def test_linreg_noniid_matches_paper_protocol():
    m, n, d = 16, 32, 800
    batch = linreg_noniid(0, d, n, m)
    assert batch["A"].shape[0] == m
    sizes = batch["mask"].sum(1)
    assert sizes.sum() == d  # all samples assigned exactly once
    base = d / m
    assert sizes.min() >= int(0.5 * base) - 1  # paper's heterogeneous d_i
    assert sizes.max() <= int(1.5 * base) + 1
    # padded rows are zero
    i = int(np.argmin(sizes))
    pad = batch["A"][i][batch["mask"][i] == 0]
    assert (pad == 0).all()


def test_dirichlet_partition_covers_all():
    labels = np.random.default_rng(0).integers(0, 10, 1000)
    parts = dirichlet_partition(labels, 8, alpha=0.5)
    allidx = np.concatenate(parts)
    assert len(allidx) == 1000 and len(np.unique(allidx)) == 1000


def test_equal_partition():
    assert sum(equal_partition(103, 8)) == 103


def test_synthetic_batch_modes():
    for arch in ("tinyllama-1.1b", "musicgen-large", "llava-next-mistral-7b"):
        cfg = ARCHITECTURES[arch].reduced()
        b = synthetic_batch_for(cfg, m=3, batch_per_client=2, seq_len=8)
        lead = jax.tree.leaves(b)[0].shape[:2]
        assert lead == (3, 2)


def test_paper_lr_schedule():
    lr = paper_lr(0.5)
    assert abs(float(lr(jnp.asarray(0))) - 0.5) < 1e-6  # log2(2) = 1
    assert float(lr(jnp.asarray(100))) < 0.08


def test_sgd_adam_reduce_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    for opt in (sgd(0.1), adam(0.2)):
        p = params
        state = opt.init(p)
        for _ in range(200):
            g = jax.grad(lambda q: jnp.sum(q["w"] ** 2))(p)
            upd, state = opt.update(g, state, p)
            p = apply_updates(p, upd)
        assert float(jnp.abs(p["w"]).max()) < 1e-2


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
        "nested": {"b": jnp.ones((4,), jnp.bfloat16), "c": jnp.asarray(3)},
    }
    d = str(tmp_path / "ckpt")
    save_checkpoint(d, 7, tree, extra={"note": "x"})
    assert latest_step(d) == 7
    restored, extra = load_checkpoint(d, 7, tree)
    assert extra["note"] == "x"
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )


def test_all_architectures_registered():
    assert len(list_architectures()) == 11
    families = {get_config(a).family for a in list_architectures()}
    assert families == {"dense", "moe", "ssm", "hybrid", "vlm", "audio"}


def test_param_counts_near_nameplate():
    expect = {
        "arctic-480b": 480e9, "deepseek-v3-671b": 671e9, "deepseek-67b": 67e9,
        "stablelm-12b": 12e9, "llava-next-mistral-7b": 7.2e9,
        "tinyllama-1.1b": 1.1e9, "qwen1.5-0.5b": 0.46e9,
        "mellum2-12b-a2.5b": 12e9,
    }
    for arch, target in expect.items():
        got = get_config(arch).param_count()
        assert 0.8 * target < got < 1.25 * target, f"{arch}: {got:.3e}"


def test_input_shapes_table():
    assert INPUT_SHAPES["train_4k"].global_batch == 256
    assert INPUT_SHAPES["long_500k"].seq_len == 524_288
    assert INPUT_SHAPES["decode_32k"].kind == "decode"
