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
agree with the reference.  The 125 rows that pad the vocabulary to a
multiple of 128 are zero, so no served token lies outside the published
vocabulary.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench import costs

# leaf tags: the embedding, the final norm, the exit norms, then one tag
# per layer (LAYER0 + layer index)
EMBED, FINAL_NORM, EXIT_NORMS, LAYER0 = 1, 2, 3, 1000


def exit_layers(cfg: Dict) -> List[int]:
    """1-based layer counts after which an exit head sits (the final head
    excluded), evenly spaced in depth as the configuration states."""
    n, L = cfg["num_exits"], cfg["num_hidden_layers"]
    return [max(1, round(L * (i + 1) / (n + 1))) for i in range(n)]


def segments(cfg: Dict) -> List[Tuple[int, int]]:
    """``(first layer, layer count)`` of each run of layers between two
    exit heads."""
    edges = [0] + exit_layers(cfg) + [cfg["num_hidden_layers"]]
    return [(a, b - a) for a, b in zip(edges[:-1], edges[1:])]


def layer_shapes(cfg: Dict) -> Dict[str, Dict[str, Tuple[tuple, int]]]:
    """``{block: {leaf: (shape, fan_in)}}`` of one layer; ``fan_in`` 0
    marks a norm weight."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"attn": {"wq": ((d, hq), d), "wk": ((d, hkv), d),
                     "wv": ((d, hkv), d), "wo": ((hq, d), hq),
                     "ln": ((d,), 0)},
            "ffn": {"wg": ((d, ff), d), "wu": ((d, ff), d),
                    "wd": ((ff, d), ff), "ln": ((d,), 0)}}


def _ints(key, shape):
    bits = jax.random.bits(key, shape, jnp.uint32)
    return (bits >> 16).astype(jnp.int32) - 32768          # [-32768, 32768)


def _matrix(key, shape, fan_in: int, dtype):
    # uniform on [-a, a) has variance a^2 / 3: a = sqrt(3 / fan_in)
    c = math.sqrt(3.0 / fan_in) / 32768.0
    return (_ints(key, shape).astype(jnp.float32) * c).astype(dtype)


def _norm(key, shape, dtype):
    # (k + 2^17) * 2^-17 is exact in float32: [0.75, 1.25)
    k = _ints(key, shape) + 131072
    return (k.astype(jnp.float32) * (2.0 ** -17)).astype(dtype)


def _leaf(key, shape, fan_in, dtype):
    return _norm(key, shape, dtype) if fan_in == 0 else \
        _matrix(key, shape, fan_in, dtype)


def layer(cfg: Dict, root, index: int, dtype) -> Dict:
    """One layer's weights (``{"attn": {...}, "ffn": {...}}``)."""
    lkey = jax.random.fold_in(root, LAYER0 + index)
    out, j = {}, 0
    for block, leaves in layer_shapes(cfg).items():
        out[block] = {}
        for name, (shape, fan) in leaves.items():
            out[block][name] = _leaf(jax.random.fold_in(lkey, j), shape,
                                     fan, dtype)
            j += 1
    return out


def embedding(cfg: Dict, root, dtype):
    V, Vp, d = cfg["vocab_size"], costs.padded_vocab(cfg), cfg["hidden_size"]
    table = _matrix(jax.random.fold_in(root, EMBED), (V, d), d, dtype)
    return jnp.concatenate([table, jnp.zeros((Vp - V, d), dtype)])


def final_norm(cfg: Dict, root, dtype):
    return _norm(jax.random.fold_in(root, FINAL_NORM),
                 (cfg["hidden_size"],), dtype)


def exit_norm(cfg: Dict, root, e: int, dtype):
    """The norm weight of exit head ``e`` (0-based, final head excluded)."""
    key = jax.random.fold_in(jax.random.fold_in(root, EXIT_NORMS), e)
    return _norm(key, (cfg["hidden_size"],), dtype)


def root_key(seed: int):
    return jax.random.key(int(seed))


def _draw(cfg_items: tuple, seed_key, dtype):
    cfg = dict(cfg_items)
    segs = []
    for first, n in segments(cfg):
        layers = [layer(cfg, seed_key, first + i, dtype) for i in range(n)]
        segs.append(jax.tree_util.tree_map(lambda *x: jnp.stack(x),
                                           *layers))
    params = {"embed": embedding(cfg, seed_key, dtype),
              "segments": tuple(segs),
              "final_norm": final_norm(cfg, seed_key, dtype)}
    if cfg["num_exits"]:
        params["exit_norms"] = jnp.stack(
            [exit_norm(cfg, seed_key, e, dtype)
             for e in range(cfg["num_exits"])])
    return params


_draw_jit = jax.jit(_draw, static_argnums=(0, 2))


def key_items(cfg: Dict) -> tuple:
    """The sizes the draw depends on, hashable (a static jit argument)."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers",
            "num_exits", "vocab_size")
    return tuple((k, cfg[k]) for k in keys)


def draw(cfg: Dict, seed: int, dtype=jnp.bfloat16) -> Dict:
    """The whole model's weights, drawn on the device in one compiled
    call, in ``dtype``, in the program's parameter layout."""
    return _draw_jit(key_items(cfg), root_key(seed), dtype)


def abstract(cfg: Dict, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda k: _draw(key_items(cfg), k, dtype),
                          root_key(0))
