"""The benchmark's work count for a Nyström solve and its peak table."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench import roofline  # noqa: E402

N, M, D, K = 1000, 64, 8, 8


def test_nystrom_flops_by_hand():
    affinity = (1000 + 64) * 64 * (2 * 8 + 3)        # 1,293,824
    gram = 2 * 1000 * 64 * 64                        # 8,192,000
    extension = 2 * 1000 * 64 * 8                    # 1,024,000
    assert roofline.nystrom_flops(N, M, D, K) == affinity + gram + extension
    assert roofline.nystrom_flops(N, M, D, K) == 10_509_824


def test_nystrom_bytes_by_hand():
    # rows in, embedding out, landmarks, W^-1/2, projector, degrees
    words = 1000 * 8 + 1000 * 8 + 64 * 8 + 64 * 64 + 64 * 8 + 64
    assert roofline.nystrom_bytes(N, M, D, K) == 4 * words == 84_736


def test_least_time_takes_the_larger_bound():
    p = roofline.peaks("TPU v5 lite")
    secs, bound = roofline.nystrom_least_s(N, M, D, K, "TPU v5 lite")
    compute = 10_509_824 / p["bf16_flops_per_s"]
    memory = 84_736 / p["hbm_bytes_per_s"]
    assert secs == pytest.approx(max(compute, memory))
    assert bound == ("compute" if compute >= memory else "memory")
    # at the benchmark's StackOverflow shape the Gram dominates: compute
    secs, bound = roofline.nystrom_least_s(342_477, 1024, 128, 16,
                                           "TPU v5 lite")
    assert bound == "compute"
    int8, _ = roofline.nystrom_least_s(342_477, 1024, 128, 16,
                                       "TPU v5 lite", "int8")
    assert int8 < secs


def test_v5e_peaks_are_the_published_ones():
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks(kind)
    with pytest.raises(KeyError):
        roofline.nystrom_least_s(N, M, D, K, kind)
