"""The benchmark's copies of the data generators give the originals'
arrays, and the seeds it derives are fixed by `--seed` alone."""
import numpy as np
import pytest

from bench import workload
from bench.generators import linreg_noniid, million_client_batch


def test_million_client_batch_matches_original():
    from benchmarks.common import million_client_batch as original

    seed = workload.seeds(2**31 + 77)["data"]
    ours = million_client_batch.million_client_batch(5000, 100, seed)
    theirs = original(5000, 100, seed)
    assert set(ours) == set(theirs)
    for k in ours:
        np.testing.assert_array_equal(ours[k], np.asarray(theirs[k]))


def test_linreg_noniid_matches_original():
    from repro.data.synthetic import linreg_noniid as original

    seed = workload.seeds(12345)["data"]
    ours = linreg_noniid.linreg_noniid(seed, 900, 48, 16)
    theirs = original(seed, 900, 48, 16)
    assert set(ours) == set(theirs)
    for k in ours:
        np.testing.assert_array_equal(ours[k], theirs[k])


def test_client_sizes_cover_the_samples():
    sizes = linreg_noniid.heterogeneous_sizes(np.random.default_rng(3),
                                             12800, 128)
    assert sum(sizes) == 12800 and len(sizes) == 128
    assert min(sizes) >= 50 and max(sizes) <= 150


def test_seeds_are_fixed_by_the_seed_and_fit_31_bits():
    a = workload.seeds(2**33 + 5)
    assert a == workload.seeds(2**33 + 5)
    assert a != workload.seeds(2**33 + 6)
    assert all(0 <= v < 2**31 for v in a.values())
    assert len(set(a.values())) == 3


def test_parallel_client_batch_depends_on_the_seed_alone(monkeypatch):
    from bench.generators import parallel_client_batch as gen

    monkeypatch.setattr(gen, "BLOCK", 1000)
    a = gen.parallel_client_batch(5500, 20, 7)
    monkeypatch.setattr(gen.os, "cpu_count", lambda: 1)
    b = gen.parallel_client_batch(5500, 20, 7)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert a["A"].shape == (5500, 1, 20) and a["b"].shape == (5500, 1)
    assert a["A"].dtype == np.float32 and np.all(a["mask"] == 1)
    assert not np.array_equal(a["A"], gen.parallel_client_batch(5500, 20,
                                                                8)["A"])
    # b = A x* + 0.1 noise: the residual of the best fit is the noise
    x, *_ = np.linalg.lstsq(a["A"][:, 0, :], a["b"][:, 0], rcond=None)
    assert np.std(a["b"][:, 0] - a["A"][:, 0, :] @ x) == \
        pytest.approx(0.1, rel=0.1)
