"""A model family is a file: a configuration names it by ``model_type``,
and a family module the harness has never seen is built, drawn, counted
and checked with no edit to the harness."""
import shutil

import jax
import numpy as np
import pytest

from bench import costs, harness, reference, weights
from bench.tests import test_bench_dense_readings as dense
from bench.tests import tiny_cell

SEED = 2**31 + 613
READERS = dense.READERS


@pytest.fixture
def copied(tmp_path, monkeypatch):
    """The tiny configuration; the same one naming ``granite_copy``, a copy
    of the granite family in a families directory of its own; and the
    call that points the harness at that directory."""
    shutil.copy(harness.FAMILIES / "granite.py", tmp_path / "granite_copy.py")
    cfg = tiny_cell.spec()["config"]
    return (cfg, dict(cfg, model_type="granite_copy"),
            lambda: monkeypatch.setattr(harness, "FAMILIES", tmp_path))


def _layout(cfg):
    p = weights.draw(cfg, SEED)
    return jax.tree_util.tree_structure(p), [
        np.asarray(x) for x in jax.tree_util.tree_leaves(p)]


def _counts(cfg):
    return [costs.weight_bytes(cfg), costs.weight_bytes(cfg, 3),
            costs.decode_flops(cfg, 2, 40),
            costs.decode_cache_bytes(cfg, 4, 99),
            costs.prefill_flops(cfg, 128), costs.prefill_bytes(cfg, 128),
            {m: harness.metric_reader(m)(dense._ctx(cfg)) for m in READERS}]


def test_the_copy_equals_granite(copied):
    cfg, copy, use_copy = copied
    granite = harness.family(cfg)
    want = (granite.model_config(cfg), _layout(cfg), _counts(cfg),
            reference.served_gaps(cfg, SEED, dense._rows(cfg))["gap"])
    use_copy()
    fam = harness.family(copy)
    assert fam is not granite and fam.__file__.endswith("granite_copy.py")
    with pytest.raises(ValueError, match="granite.py"):
        harness.family(cfg)
    assert fam.model_config(copy) == want[0]
    tree, leaves = _layout(copy)
    assert tree == want[1][0]
    assert all(np.array_equal(a, b) for a, b in zip(leaves, want[1][1]))
    assert _counts(copy) == want[2]
    assert np.array_equal(
        reference.served_gaps(copy, SEED, dense._rows(copy))["gap"], want[3])


def test_a_harness_run_serves_the_copy(copied, monkeypatch):
    """The whole run on the CPU: the engine is built from the copy's
    configuration, serves the copy's weights and is checked against the
    copy's reference."""
    _, copy, use_copy = copied
    use_copy()
    spec = tiny_cell.spec()
    spec["config"] = copy
    monkeypatch.setattr(tiny_cell, "spec", lambda **_: spec)
    res = tiny_cell.run(seed=SEED)
    assert res["correct"], res["checks"]


def test_an_unknown_model_type_names_the_missing_file():
    cfg = dict(tiny_cell.spec()["config"], model_type="granitemoehybrid")
    with pytest.raises(ValueError, match="bench/families/granitemoehybrid.py"):
        harness.family(cfg)
    with pytest.raises(ValueError, match="granitemoehybrid"):
        weights.draw(cfg, 1)
