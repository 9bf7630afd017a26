"""``harness.percentile``: the plain estimate of a percentile."""
import pytest

from benchmark.lib.harness import percentile


def test_interpolates_between_the_order_statistics_around_the_rank():
    x = [float(v) for v in range(1, 22)]             # 21 values, 1..21
    assert percentile(x, 95) == pytest.approx(20.0)  # rank 0.95 x 20 = 19
    assert percentile(x, 50) == pytest.approx(11.0)
    assert percentile(x[::-1], 95) == percentile(x, 95)      # order-free
    assert percentile([1.0, 2.0], 95) == pytest.approx(1.95)


def test_small_samples():
    assert percentile([], 95) is None
    assert percentile([3.0], 95) == 3.0
    assert percentile([1.0, 2.0], 50) == pytest.approx(1.5)
