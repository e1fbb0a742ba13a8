"""Weights from the seed, in the layout the program serves them from.

Every leaf is drawn from its own key, ``fold_in(fold_in(key(seed), tag),
index)``, as integers that are turned into floats with a single rounding
each: a uniform integer ``k`` in [-32768, 32768) becomes ``bf16(f32(k) *
c)``.  No step of that can round differently in another program, so the
reference, which draws each layer again on its own, gets the very values
the whole-model draw handed to the program.

Matrices are uniform with the variance of ``1 / fan_in`` (the program's
own initialiser draws a normal of that variance); norm weights are
uniform in [0.75, 1.25), so a path that skipped a norm's scale would not
agree with the reference.

What the leaves are is the configuration's family's
(``bench/families/<model_type>.py``): each layer's leaves, each run of
layers between two exits as the program's segment holds it, the
embedding and the final and exit norms.  This module draws the integers
and puts the segments and heads together into the program's tree.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.harness import family


def exit_layers(cfg: Dict) -> List[int]:
    """1-based layer counts after which an exit head sits (the final head
    excluded), as the configuration states them (the harness refuses a
    program that places them elsewhere)."""
    return list(cfg["exit_after_layers"])


def segments(cfg: Dict) -> List[Tuple[int, int]]:
    """``(first layer, layer count)`` of each run of layers between two
    exit heads."""
    edges = [0] + exit_layers(cfg) + [cfg["num_hidden_layers"]]
    return [(a, b - a) for a, b in zip(edges[:-1], edges[1:])]


def _ints(key, shape):
    bits = jax.random.bits(key, shape, jnp.uint32)
    return (bits >> 16).astype(jnp.int32) - 32768          # [-32768, 32768)


def matrix(key, shape, fan_in: int, dtype):
    # uniform on [-a, a) has variance a^2 / 3: a = sqrt(3 / fan_in)
    c = math.sqrt(3.0 / fan_in) / 32768.0
    return (_ints(key, shape).astype(jnp.float32) * c).astype(dtype)


def norm(key, shape, dtype):
    # (k + 2^17) * 2^-17 is exact in float32: [0.75, 1.25)
    k = _ints(key, shape) + 131072
    return (k.astype(jnp.float32) * (2.0 ** -17)).astype(dtype)


def leaves(key, shapes: Dict[str, Dict[str, Tuple[tuple, int]]], dtype
           ) -> Dict:
    """The leaves of ``shapes`` (``{block: {leaf: (shape, fan_in)}}``,
    ``fan_in`` 0 for a norm weight), leaf ``j`` in order drawn from
    ``fold_in(key, j)``."""
    out, j = {}, 0
    for block, group in shapes.items():
        out[block] = {}
        for name, (shape, fan) in group.items():
            k = jax.random.fold_in(key, j)
            out[block][name] = norm(k, shape, dtype) if fan == 0 else \
                matrix(k, shape, fan, dtype)
            j += 1
    return out


def root_key(seed: int):
    return jax.random.key(int(seed))


def stack(layers: List[Dict]) -> Dict:
    """Layers of one layout as one tree, each leaf stacked over them."""
    return jax.tree_util.tree_map(lambda *x: jnp.stack(x), *layers)


def _draw(fam, cfg_items: tuple, segs: tuple, root, dtype):
    cfg = dict(cfg_items)
    params = {"embed": fam.embedding(cfg, root, dtype),
              "segments": tuple(fam.segment(cfg, root, first, n, dtype)
                                for first, n in segs),
              "final_norm": fam.final_norm(cfg, root, dtype)}
    if len(segs) > 1:
        params["exit_norms"] = jnp.stack(
            [fam.exit_norm(cfg, root, e, dtype) for e in range(len(segs) - 1)])
    return params


_draw_jit = jax.jit(_draw, static_argnums=(0, 1, 2, 4))


def _statics(cfg: Dict) -> tuple:
    """The draw's static arguments: the family module, the sizes it
    draws from (hashable) and the segments."""
    fam = family(cfg)
    return fam, fam.key_items(cfg), tuple(segments(cfg))


def draw(cfg: Dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """The whole model's weights, drawn on the device in one compiled
    call, in ``dtype``, in the program's parameter layout."""
    return _draw_jit(*_statics(cfg), root_key(seed), dtype)


def abstract(cfg: Dict, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda k: _draw(*_statics(cfg), k, dtype),
                          root_key(0))
