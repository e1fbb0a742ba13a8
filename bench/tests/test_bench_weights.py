"""The seed's weights: the program's layout, the same values whether the
whole model is drawn at once or a layer at a time, zero padding rows."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, weights
from bench.tests import tiny_cell


def test_layout_matches_the_program():
    cfg = tiny_cell.spec()["config"]
    from repro.configs import get_config
    from repro.models import Model
    model = Model(get_config(harness.register_config(cfg)))
    have = jax.eval_shape(lambda k: model.init_params(k, jnp.bfloat16),
                          jax.random.key(0))
    want = weights.abstract(cfg)
    assert jax.tree_util.tree_structure(have) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(have),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_whole_draw_equals_layer_by_layer():
    cfg = tiny_cell.spec()["config"]
    seed = 2**31 + 12345
    p = weights.draw(cfg, seed)
    root = weights.root_key(seed)
    i = 0
    for seg, (first, n) in enumerate(weights.segments(cfg)):
        for j in range(n):
            one = weights.layer(cfg, root, first + j, jnp.bfloat16)
            got = jax.tree_util.tree_map(lambda x: x[j],
                                         p["segments"][seg])
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(one)):
                assert np.array_equal(np.asarray(a), np.asarray(b))
            i += 1
    assert i == cfg["num_hidden_layers"]
    V = cfg["vocab_size"]
    assert not np.asarray(p["embed"][V:]).any()
    assert np.asarray(p["embed"][:V]).std() > 0
    other = weights.draw(cfg, seed + 1)
    assert not np.array_equal(np.asarray(p["embed"]),
                              np.asarray(other["embed"]))
