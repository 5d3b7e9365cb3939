"""The percentile arithmetic equals numpy's default, which the engine's
own accounting uses."""
import numpy as np
import pytest

from yardstick import stats


@pytest.mark.parametrize("n", [1, 2, 7, 240])
def test_percentile_is_numpys(n):
  x = np.random.default_rng(n).exponential(100.0, n)
  for p in (0, 50, 95, 100):
    assert stats.percentile(x, p) == pytest.approx(np.percentile(x, p))


def test_no_values_is_an_error():
  with pytest.raises(ValueError):
    stats.percentile([], 50)
