"""The benchmark's rate and traffic arithmetic."""

import pytest

import stats
import traffic


def test_rate_is_all_tokens_over_all_window_time():
    assert stats.rate(300, 10.0, 12.0) == 150.0
    with pytest.raises(ValueError):
        stats.rate(1, 2.0, 2.0)


CLOSED = {"kind": "closed", "requests": 8,
          "prompt": {"dist": "fixed", "tokens": 32},
          "output": {"dist": "fixed", "tokens": 16}}


def test_traffic_is_a_function_of_the_seed():
    a = traffic.generate(CLOSED, 2 ** 33 + 5, 1000)
    b = traffic.generate(CLOSED, 2 ** 33 + 5, 1000)
    c = traffic.generate(CLOSED, 5, 1000)
    assert [r.prompt.tolist() for r in a] == [r.prompt.tolist() for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


def test_closed_mix_submits_everything_at_once():
    reqs = traffic.generate(CLOSED, 3, 100)
    assert len(reqs) == 8
    assert all(r.prompt_len == 32 and r.max_tokens == 16 for r in reqs)
    assert all(0 <= t < 100 for r in reqs for t in r.prompt.tolist())
    assert len({tuple(r.prompt.tolist()) for r in reqs}) == 8


def test_an_unknown_mix_is_refused():
    with pytest.raises(ValueError, match="kind"):
        traffic.generate(dict(CLOSED, kind="open"), 3, 100)
    with pytest.raises(ValueError, match="dist"):
        traffic.generate(dict(CLOSED, prompt={"dist": "lognormal"}), 3, 100)
