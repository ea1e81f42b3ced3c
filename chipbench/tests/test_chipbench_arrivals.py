"""The open-loop arrival schedule: rate, seeding and due times."""
import numpy as np
import pytest

from chipbench import arrivals


@pytest.mark.parametrize("rate,seconds", [(1.0, 51.0), (2.5, 51.0), (40.0, 10.0)])
def test_rate_and_window(rate, seconds):
    due = arrivals.open_schedule(rate, seconds, 1207)
    assert due[0] == 0.0
    assert np.all(np.diff(due) > 0)
    assert due[-1] < seconds
    # quantile gaps keep the offered count within a few of rate * seconds
    assert abs(len(due) - rate * seconds) <= 0.05 * rate * seconds + 2


def test_same_schedule_seed_same_times():
    a = arrivals.open_schedule(1.3, 51.0, 7)
    b = arrivals.open_schedule(1.3, 51.0, 7)
    c = arrivals.open_schedule(1.3, 51.0, 8)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)


def test_gaps_are_exponential_quantiles():
    rate, seconds = 2.0, 100.0
    n = round(rate * seconds)
    due = arrivals.open_schedule(rate, seconds, 3)
    want = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    # each gap between kept due times is one of the n quantiles
    gaps = np.diff(due)
    assert np.abs(gaps[:, None] - want[None, :]).min(axis=1).max() < 1e-9
    assert abs(want.mean() - 1 / rate) < 0.05 / rate


def test_bad_rate_refused():
    with pytest.raises(ValueError):
        arrivals.open_schedule(0.0, 10.0, 1)
