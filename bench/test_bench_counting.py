"""Operation and byte counts against counts made by hand."""
import pytest

from bench import counting


@pytest.mark.parametrize("p,mults", [(1, 0), (2, 1), (3, 2), (4, 2),
                                     (5, 3), (8, 3), (9, 4)])
def test_int_pow_mults(p, mults):
    assert counting.int_pow_mults(p) == mults


def test_kernel_bytes_by_hand():
    # m=3 clients, n=100 features: rows of 100 f32 = 400 B, whatever the
    # program pads them to; 4 streams read + 3 written = 7 * 3 * 400; the
    # select is one int32 a client; sigma and 1/m are two f32
    assert counting.kernel_bytes(3, 100) == 7 * 3 * 400 + 3 * 4 + 8
    assert counting.kernel_bytes(10**6, 100) == \
        7 * 10**6 * 400 + 4 * 10**6 + 8


def test_round_flops_by_hand():
    # k0=5: the kernel does 3+2+1+2+3+3+2 = 16 per coordinate (a^4 is two
    # squarings); the round adds 1+1+1+2+5 = 10; gradients 4 n per sample
    assert counting.kernel_elementwise_ops(5) == 16
    assert counting.round_flops(2, 3, 10, 5) == 4 * 3 * 10 + 2 * 3 * 26
    # k0=2: a^1 costs nothing
    assert counting.kernel_elementwise_ops(2) == 14


def test_for_config_uses_real_sizes():
    cfg = {"num_clients": 128, "dim": 1024, "samples": 8992, "k0": 5,
           "problem": "linreg"}
    got = counting.for_config(cfg)
    assert got["flops_per_round"] == 4 * 1024 * 8992 + 128 * 1024 * 26
    assert got["kernel_bytes_per_round"] == 7 * 128 * 4096 + 128 * 4 + 8
