"""The generator: a seed fixes the requests; other seeds permute the same
gaps, tenants, lengths and devices within blocks, and draw other
prompts."""
import copy
import types

import numpy as np
import pytest

from bench import traffic as T
from bench.tests import tiny_cell


def _make(t, seed, vocab=300):
    return T.make_requests(t, vocab_size=vocab, seed=seed,
                           factory=types.SimpleNamespace)


@pytest.fixture
def tiny():
    return copy.deepcopy(tiny_cell.spec()["traffic"])


def test_same_seed_same_requests(tiny):
    a, b = _make(tiny, 2**31 + 9), _make(tiny, 2**31 + 9)
    assert [vars(x)["arrival_s"] for x in a] == \
        [vars(x)["arrival_s"] for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_other_seed_same_work_in_another_order(tiny):
    a, b = _make(tiny, 1), _make(tiny, 2)
    block = tiny["permute_block"]
    for lo in range(0, len(a), block):
        key = lambda r: (r.tenant, r.prompt_len, r.max_new_tokens,  # noqa
                         r.device)
        assert sorted(map(key, a[lo:lo + block])) == \
            sorted(map(key, b[lo:lo + block]))
    assert a[-1].arrival_s == pytest.approx(b[-1].arrival_s)
    assert [r.tenant for r in a] != [r.tenant for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert all(0 <= r.device < tiny["topology"]["devices"] for r in a)


def test_block_of_one_keeps_the_schedule_for_every_seed(tiny):
    tiny["permute_block"] = 1
    a, b = _make(tiny, 1), _make(tiny, 2**31 + 77)
    sched = lambda r: (r.arrival_s, r.tenant, r.prompt_len,  # noqa: E731
                       r.max_new_tokens, r.device)
    assert list(map(sched, a)) == list(map(sched, b))
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_rate_and_mix(tiny):
    reqs = _make(tiny, 3)
    span = reqs[-1].arrival_s - reqs[0].arrival_s
    rate = (len(reqs) - 1) / span
    assert rate == pytest.approx(tiny["arrivals"]["rate_hz"], rel=0.1)
    long_share = np.mean([r.tenant == "long" for r in reqs])
    assert long_share == pytest.approx(0.5, abs=0.05)


def test_lognormal_lengths_and_onoff_arrivals(tiny):
    tiny["prompt_len"] = {"lognormal": {"median": 64, "sigma": 1.0,
                                        "min": 8, "max": 512}}
    tiny["arrivals"] = {"process": "onoff", "rate_hz": 40.0, "on_s": 1.0,
                        "off_s": 4.0}
    reqs = _make(tiny, 4)
    lens = np.asarray([r.prompt_len for r in reqs])
    assert lens.min() >= 8 and lens.max() <= 512
    assert np.median(lens) == pytest.approx(64, rel=0.15)
    assert all(len(r.prompt) == r.prompt_len for r in reqs)
    gaps = np.diff([r.arrival_s for r in reqs])
    assert (gaps > 1.0).mean() > 0.01          # the off periods
