"""The window's arithmetic: tails over all samples, stamps outside the
window dropped, and the clock that ends a run."""
import math
import types

import numpy as np
import pytest

from bench import window as W


def _req(arrival, stamps):
    r = types.SimpleNamespace(arrival_wall=arrival)
    r.tokens = types.SimpleNamespace(stamps=list(stamps))
    return r


def test_tokens_ttft_itl_keep_only_the_window():
    reqs = [_req(0.5, [0.9, 1.2, 1.4, 2.1]),      # first token before t0
            _req(1.1, [1.3, 1.6, 1.9]),
            _req(1.8, [1.95, 2.05]),               # second token after t1
            _req(None, [1.2, 1.3])]                 # device tier: ignored
    s = W.summary(reqs, 1.0, 2.0)
    assert s["tokens"] == 2 + 3 + 1
    assert s["tokens_per_s"] == pytest.approx(6.0)
    edge = reqs[:3]
    assert sorted(W.ttft_samples(edge, 1.0, 2.0)) == pytest.approx(
        [0.15, 0.2])
    assert s["ttft_n"] == 2 and s["itl_n"] == 3
    assert sorted(W.itl_samples(edge, 1.0, 2.0)) == pytest.approx(
        [0.2, 0.3, 0.3])


@pytest.mark.parametrize("q", [50, 90, 95, 99])
def test_percentile_is_over_all_samples(q):
    rng = np.random.default_rng(q)
    x = rng.exponential(size=1001)
    assert W.percentile(list(x), q) == pytest.approx(np.percentile(x, q))
    assert W.percentile([], q) is None


def test_stamp_list_refuses_a_token_after_the_deadline():
    clock = W.Clock()
    owner = types.SimpleNamespace(clock=clock)
    toks = W.StampList(owner)
    toks.append(5)
    clock.deadline = -math.inf
    with pytest.raises(W.WindowClosed):
        toks.append(6)
    assert list(toks) == [5] and len(toks.stamps) == 1
