"""The float32 reference against the program's own block in float32 (at
a small size, on the CPU), and the int8 control against the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, weights
from bench.tests import tiny_cell


def _greedy(cfg, seed, prompts, n_new, head):
    """Tokens the program's model chooses in float32: prefill, then
    ``n_new`` cached steps at exit ``head``."""
    from repro.configs import get_config
    from repro.models import Model
    model = Model(get_config(harness.register_config(cfg)))
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                    weights.draw(cfg, seed))
    ep = None if head == cfg["num_exits"] + 1 else head - 1
    rows = []
    for prompt in prompts:
        P = len(prompt)
        cache = model.init_cache(1, P + n_new + 1, dtype=jnp.float32)
        h, cache = model.prefill(params, jnp.asarray(prompt[None]), cache)
        first = int(jnp.argmax(model.logits(params, h)[0, -1]))
        tok, toks = first, []
        for i in range(n_new):
            h, cache, _ = model.decode_step(
                params, cache, jnp.asarray([[tok]], jnp.int32),
                jnp.asarray(P + i, jnp.int32), exit_point=ep)
            tok = int(jnp.argmax(model.logits(params, h)[0, -1]))
            toks.append(tok)
        rows.append({"prompt": prompt, "first": first, "tokens": toks,
                     "head": head})
    return rows


@pytest.fixture(scope="module")
def served():
    cfg = tiny_cell.spec()["config"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], 12).astype(np.int32)
               for _ in range(3)]
    return cfg, {head: _greedy(cfg, 77, prompts, 8, head)
                 for head in (1, cfg["num_exits"] + 1)}


@pytest.mark.parametrize("head", [1, 2])
def test_reference_agrees_with_the_program_in_float32(served, head):
    cfg, rows = served
    g = reference.served_gaps(cfg, 77, rows[head])
    assert len(g["gap"]) == 3 * 9
    assert g["gap"].max() < 1e-4


def test_a_changed_token_is_seen(served):
    cfg, rows = served
    bad = [dict(r, tokens=list(r["tokens"])) for r in rows[2]]
    bad[1]["tokens"][3] = (bad[1]["tokens"][3] + 1) % cfg["vocab_size"]
    assert reference.served_gaps(cfg, 77, bad)["gap"].max() > 0.5


def test_int8_control_departs_from_the_reference(served):
    """At this size the control's first choices differ from the
    reference's at a few positions, where the program's float32 choices
    differ nowhere; the readings at the cells' own size are in PERF.md."""
    cfg, rows = served
    both = rows[2] + rows[1]
    prog = reference.served_gaps(cfg, 77, both)["gap"]
    ctrl = reference.control_gaps(cfg, 77, both)["gap"]
    assert (ctrl > 0).sum() >= 1
    assert ctrl.max() > 10 * prog.max()
