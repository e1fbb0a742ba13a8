"""The dense granite block reads as it did before its code moved into
``bench/families/granite.py``: the same weights bit for bit, the same
reference and control gaps bit for bit, and the same per-layer readings.

``data/dense-readings.json`` was written by :func:`readings` at the commit
before the move, on the CPU; it is a record, not a file to refresh.
"""
import hashlib
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness, reference, weights

DATA = Path(__file__).resolve().parent / "data"
RECORD = DATA / "dense-readings.json"
SEED = 2**31 + 4099
READERS = ("decode_step_roofline", "prefill_roofline", "mfu", "prefill_mfu")
CONFIGS = ("granite-3-2b", "granite-3-8b-d20")


def _tiny():
    return json.loads((DATA / "tiny-config.json").read_text())


def _rows(cfg):
    """Five served requests with tokens drawn at random (so most gaps are
    far from 0), heads at the exit and at the end."""
    rng = np.random.default_rng(4099)
    V, heads = cfg["vocab_size"], [1, cfg["num_exits"] + 1]
    return [{"prompt": rng.integers(0, V, P).tolist(),
             "first": int(rng.integers(0, V)),
             "tokens": rng.integers(0, V, 6).tolist(),
             "head": heads[i % 2]}
            for i, P in enumerate((12, 7, 12, 9, 5))]


def _ctx(cfg):
    """A traced window of 5 s with six edge-served requests that arrive
    across its start (two prompt lengths, every head) and one that the
    device tier served."""
    reqs = [SimpleNamespace(
        prompt_len=(128, 1024)[k % 2],
        exit_point=k % (cfg["num_exits"] + 1) + 1,
        arrival_wall=8.0 + 0.9 * k,
        tokens=SimpleNamespace(stamps=[8.3 + 0.9 * k + 0.25 * i
                                       for i in range(20)]))
        for k in range(6)]
    reqs.append(SimpleNamespace(prompt_len=128, exit_point=1,
                                arrival_wall=None,
                                tokens=SimpleNamespace(stamps=[11.0])))
    return {"config": cfg, "requests": reqs, "arena": {"calls": 40},
            "window": {"t0": 10.0, "t1": 15.0, "seconds": 5.0},
            "peaks": harness.device_peaks("TPU v5 lite"),
            "trace": {"modules": {"jit_astep": {"seconds": 1.5, "count": 40},
                                  "jit_prefill": {"seconds": 0.4,
                                                  "count": 3}}}}


def readings():
    """Everything the record holds, computed by the tree at hand."""
    cfg = _tiny()
    p = weights.draw(cfg, SEED)
    sums = {jax.tree_util.keystr(k): hashlib.sha256(
        np.asarray(x).tobytes()).hexdigest()
        for k, x in jax.tree_util.tree_leaves_with_path(p)}
    rows = _rows(cfg)
    gaps = {"served": reference.served_gaps(cfg, SEED, rows)["gap"],
            "control": reference.control_gaps(cfg, SEED, rows)["gap"]}
    readers = {}
    for name in CONFIGS:
        full = harness.load(harness.BENCH / "configs" / f"{name}.json")
        readers[name] = {m: harness.metric_reader(m)(_ctx(full))
                         for m in READERS}
    return {"weights": sums,
            "gaps": {k: np.asarray(v, np.float64).tolist()
                     for k, v in gaps.items()},
            "readers": readers}


@pytest.fixture(scope="module")
def pair():
    return json.loads(RECORD.read_text()), readings()


def test_weights_are_the_same_bit_for_bit(pair):
    record, now = pair
    assert now["weights"] == record["weights"]


@pytest.mark.parametrize("kind", ["served", "control"])
def test_gaps_are_the_same_bit_for_bit(pair, kind):
    record, now = pair
    want = np.asarray(record["gaps"][kind], np.float32)
    assert len(want) == 5 + 5 * 6 and want.any()
    assert np.array_equal(np.asarray(now["gaps"][kind], np.float32), want)


@pytest.mark.parametrize("config", CONFIGS)
def test_readers_give_the_same_values(pair, config):
    record, now = pair
    assert all(v is not None for v in record["readers"][config].values())
    assert now["readers"][config] == record["readers"][config]
