import numpy as np
import pytest

from bench import stats
from bench.serve import Req


def test_percentile_matches_numpy_linear():
    rng = np.random.default_rng(0)
    xs = list(rng.exponential(1.0, 97))
    for q in (0, 10, 50, 90, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_refuses_empty():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _req(token_t):
    return Req(rid=0, prompt=np.zeros(1), max_new=4, token_t=list(token_t))


@pytest.mark.parametrize("window, gaps, count", [
    ((1.0, 10.0), [1.0, 0.5, 2.0], 3),
    ((0.0, 20.0), [1.0, 0.5, 2.0, 7.0], 5),
    ((4.5, 10.0), [], 0),
])
def test_inter_token_gaps_and_tokens_in_window(window, gaps, count):
    reqs = [_req([0.5, 1.5, 2.0, 4.0, 11.0])]
    assert stats.inter_token_gaps(reqs, *window) == pytest.approx(gaps)
    assert stats.tokens_in(reqs, *window) == count


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)
    # the run farthest from the median (100) goes; 2..6 remain
    assert stats.trimmed_spread([2, 3, 100, 4, 5, 6]) == pytest.approx((5.5 - 2.5) / 4)


def test_sets_report_one_line_per_metric_and_set_of_three_or_more():
    from bench import sets

    readings = [{"a": [2.0, 3.0, 100.0, 4.0, 5.0, 6.0], "b": [1.0, 2.0]},
                {"a": [4.0, 6.0, 8.0, 10.0, 12.0, 200.0], "b": [1.0, 2.0, 3.0]}]
    lines = sets.report(readings)
    assert [ln.split(":")[0] for ln in lines] == ["a set 1", "a set 2", "b set 2"]
    assert f"vs set 1 {2.0!r}" in lines[1]
