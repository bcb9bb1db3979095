"""End-to-end behaviour tests: the public drivers do real work on CPU."""
import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_train_driver_linreg_end_to_end(monkeypatch):
    from repro.launch import train as train_mod

    # the persistent compile cache is the caller's to place, not a test's
    monkeypatch.setattr(train_mod, "use_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "train", "--problem", "linreg", "--algo", "fedgia", "--clients", "16",
        "--k0", "5", "--rounds", "100", "--dim", "40", "--samples", "1600",
        "--tol", "1e-9",
    ])
    train_mod.main()


def test_train_driver_transformer_loss_improves(tmp_path):
    """Federated LM training on a reduced arch: loss must go DOWN."""
    from repro.launch.train import train

    args = argparse.Namespace(
        problem="linreg", arch="tinyllama-1.1b", reduced=True, algo="fedgia",
        clients=4, k0=3, alpha=1.0, sigma_t=0.3, h_policy="scalar",
        unrolled=False, lr=0.01, rounds=30, tol=0.0, dim=0, samples=0,
        batch=2, seq_len=32, seed=0, log_every=10,
        checkpoint_dir=str(tmp_path / "ck"),
    )
    result = train(args)
    hist = result["history"]
    assert hist[-1]["f"] < hist[0]["f"], (
        f"loss did not improve: {hist[0]['f']} -> {hist[-1]['f']}"
    )
    assert np.isfinite(hist[-1]["f"])
    # checkpoint was written and is reloadable
    from repro.checkpoint import latest_step

    assert latest_step(str(tmp_path / "ck")) == len(hist)


def test_train_driver_no_scan_matches_engine():
    """--no-scan (legacy loop) and the scan engine agree end to end."""
    from repro.launch.train import train

    common = dict(
        problem="linreg", arch=None, reduced=False, algo="fedgia",
        clients=8, k0=3, alpha=0.5, sigma_t=0.2, h_policy="scalar",
        unrolled=False, lr=0.01, rounds=12, tol=0.0, dim=24, samples=480,
        batch=2, seq_len=32, seed=0, log_every=100, checkpoint_dir="",
    )
    res_scan = train(argparse.Namespace(**common))
    res_loop = train(argparse.Namespace(**common, no_scan=True))
    assert res_scan["rounds"] == res_loop["rounds"] == 12
    np.testing.assert_allclose(res_scan["final_f"], res_loop["final_f"],
                               rtol=1e-6)
    np.testing.assert_allclose(res_scan["final_err"], res_loop["final_err"],
                               rtol=1e-5)


def test_serve_driver_end_to_end():
    from repro.launch.serve import serve

    args = argparse.Namespace(
        arch="tinyllama-1.1b", reduced=True, batch=3, prompt_len=12, gen=6,
        long_context=False, seed=0,
    )
    gen = serve(args)
    assert gen.shape == (3, 6)
    assert (gen >= 0).all()
    # the scan-compiled decode loop generates the same tokens as the
    # legacy per-token dispatch
    gen_loop = serve(argparse.Namespace(**vars(args), no_scan=True))
    np.testing.assert_array_equal(gen, gen_loop)


def test_hlo_collective_parser():
    from repro.launch.hlo_analysis import collective_bytes, roofline_terms

    hlo = """
  %all-reduce.1 = f32[8,4096]{1,0} all-reduce(f32[8,4096]{1,0} %x), replica_groups={}
  %ag = bf16[16,128]{1,0} all-gather(bf16[8,128]{1,0} %y), dimensions={0}
  %arstart = f32[100]{0} all-reduce-start(f32[100]{0} %z)
  %ardone = f32[100]{0} all-reduce-done(f32[100]{0} %arstart)
  %add.5 = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
"""
    c = collective_bytes(hlo)
    assert c["all-reduce"] == 8 * 4096 * 4 + 400  # start counted once
    assert c["all-gather"] == 16 * 128 * 2
    terms = roofline_terms({"flops": 1e12, "bytes accessed": 1e9}, c)
    assert terms["bottleneck"] in ("compute", "memory", "collective")
    assert terms["t_compute_s"] == pytest.approx(1e12 / 197e12)


def test_dryrun_input_specs_cover_all_modes():
    from repro.config import INPUT_SHAPES
    from repro.configs import get_config
    from repro.launch.dryrun import input_specs

    for arch in ("tinyllama-1.1b", "musicgen-large", "llava-next-mistral-7b"):
        cfg = get_config(arch)
        for shape in INPUT_SHAPES.values():
            sds = input_specs(cfg, shape, num_clients=16)
            leaves = jax.tree.leaves(sds)
            assert leaves, f"{arch}/{shape.name}: empty specs"
            for l in leaves:
                assert isinstance(l, jax.ShapeDtypeStruct)
        # vlm/audio: embeds present where required
        if cfg.input_mode != "tokens":
            assert "embeds" in input_specs(cfg, INPUT_SHAPES["train_4k"], 16)
