"""The command exits non-zero, and prints no result, where it cannot
measure: on a CPU, and in a directory that holds only the benchmark."""
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARGS = ["--workload", "paper_v1.solve", "--seed", "2147483700", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_cpu_run_fails_without_result():
    p = _run(ROOT, {"PYTHONPATH": "src"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU found" in p.stderr


def test_benchmark_alone_fails_without_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_workload_fails_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "nope",
                        "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("kind", ["TPU v4", "cpu"])
def test_unknown_device_kind_is_an_error(kind):
    from bench import device

    with pytest.raises(device.DeviceError, match="peaks.json"):
        device.peaks_for(kind)


def test_v5e_peaks():
    from bench import device

    p = device.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
