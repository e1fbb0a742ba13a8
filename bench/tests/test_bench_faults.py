"""A whole run of the harness on the CPU (the look for a chip skipped),
sound and with the timed path broken underneath: ``correct`` has to come
out true for the sound run and false for each fault the cells can have.
The cells run on one chip, so no exchange between chips can be left
out."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests import tiny_cell


def _state_unchanged(monkeypatch):
    """The arena step returns the cache it was given."""
    from repro.serving.engine import CoInferenceStepper
    orig = CoInferenceStepper.decode_fn_arena

    def patched(self, graph_exit, arena):
        fn = orig(self, graph_exit, arena)

        def step(p, cache, tok, pos, mask):
            h, _ = fn(p, jax.tree_util.tree_map(jnp.copy, cache), tok, pos,
                      mask)
            return h, cache
        return step
    monkeypatch.setattr(CoInferenceStepper, "decode_fn_arena", patched)


def _half_batch(monkeypatch):
    """Every other active row of an arena call is left out of the commit:
    its keys and values are never written."""
    from repro.serving.engine import CoInferenceStepper
    orig = CoInferenceStepper.decode_fn_arena

    def patched(self, graph_exit, arena):
        fn = orig(self, graph_exit, arena)

        def step(p, cache, tok, pos, mask):
            keep = (jnp.arange(mask.shape[0]) % 2) == 0
            return fn(p, cache, tok, pos, mask & keep)
        return step
    monkeypatch.setattr(CoInferenceStepper, "decode_fn_arena", patched)


def _token_altered(monkeypatch):
    """The epilogue's logits put one fixed token first for one row in
    three."""
    from repro.models.api import Model
    orig = Model.logits

    def patched(self, params, hidden):
        out = orig(self, params, hidden)
        rows = (jnp.arange(out.shape[0]) % 3 == 1)[:, None, None]
        hit = jnp.arange(out.shape[-1]) == 7
        return jnp.where(rows & hit, out.max() + 10.0, out)
    monkeypatch.setattr(Model, "logits", patched)


def test_sound_run_is_correct():
    res = tiny_cell.run(seed=2**31 + 3)
    assert res["correct"], res["checks"]
    assert res["checks"]["device_tier_requests"]["value"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = tiny_cell.run(seed=5)
    assert not res["correct"], res["checks"]
    assert res["checks"]["mean_logit_gap"]["value"] > \
        res["checks"]["mean_logit_gap"]["limit"]


def test_a_workload_that_ends_before_the_window_is_refused(monkeypatch):
    from bench import harness
    small = tiny_cell.spec()
    small["traffic"]["requests"] = 12
    monkeypatch.setattr(tiny_cell, "spec", lambda **_: small)
    with pytest.raises(harness.WorkloadExhausted):
        tiny_cell.run(seconds=60.0)
