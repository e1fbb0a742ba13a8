"""The dense granite block (Hugging Face ``model_type`` ``granite``): its
program configuration, its weights' layout, its float32 forward and its
counts of operations and bytes.

The block is the program's block, which departs from the published
granite model in the same way (the configuration files list how): RMS
norm before attention and before a SwiGLU feed-forward, rotary positions
on the two halves of each head, softmax scaled by ``1/sqrt(head_dim)``,
no embedding, attention, residual or logits multipliers, and a head tied
to the embedding after the final norm or after an exit head's own norm.

Counts assume bf16 weights and cache (2 bytes an element).  A layer holds
four attention projections and three feed-forward matrices, and a cache
entry of every KV head; a token through ``n`` layers costs two operations
per weight it meets, and attention over ``t`` cached positions costs
``4 * heads * head_dim * t`` per layer (scores and the weighted sum).  The
head is tied to the embedding: ``2 * hidden * vocab`` per token that is
read out.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench import reference as R
from bench import weights as W

BYTES = 2          # bfloat16

# leaf tags: the embedding, the final norm, the exit norms, then one tag
# per layer (LAYER0 + layer index)
EMBED, FINAL_NORM, EXIT_NORMS, LAYER0 = 1, 2, 3, 1000


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` of the file's configuration."""
    from repro.config import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], num_exits=cfg["num_exits"],
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        source=cfg["source"])


# ----------------------------------------------------------------- layout
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


def key_items(cfg: Dict) -> tuple:
    """The sizes the draw depends on, hashable (a static jit argument)."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "num_hidden_layers",
            "num_exits", "vocab_size")
    return tuple((k, cfg[k]) for k in keys)


def layer(cfg: Dict, root, index: int, dtype) -> Dict:
    """One layer's weights (``{"attn": {...}, "ffn": {...}}``)."""
    return W.leaves(jax.random.fold_in(root, LAYER0 + index),
                    layer_shapes(cfg), dtype)


def segment(cfg: Dict, root, first: int, n: int, dtype) -> Dict:
    """Layers ``first .. first + n - 1`` as the program's segment holds
    them: every leaf stacked over the layers."""
    return W.stack([layer(cfg, root, first + i, dtype) for i in range(n)])


def embedding(cfg: Dict, root, dtype):
    """The tied embedding; the rows that pad the vocabulary to a multiple
    of 128 are zero, so no served token lies outside the published
    vocabulary."""
    V, Vp, d = cfg["vocab_size"], padded_vocab(cfg), cfg["hidden_size"]
    table = W.matrix(jax.random.fold_in(root, EMBED), (V, d), d, dtype)
    return jnp.concatenate([table, jnp.zeros((Vp - V, d), dtype)])


def final_norm(cfg: Dict, root, dtype):
    return W.norm(jax.random.fold_in(root, FINAL_NORM),
                  (cfg["hidden_size"],), dtype)


def exit_norm(cfg: Dict, root, e: int, dtype):
    """The norm weight of exit head ``e`` (0-based, final head excluded)."""
    key = jax.random.fold_in(jax.random.fold_in(root, EXIT_NORMS), e)
    return W.norm(key, (cfg["hidden_size"],), dtype)


# ---------------------------------------------------------------- forward
def dims(cfg: Dict) -> tuple:
    """The forward's static sizes: heads, KV heads, head size, norm
    epsilon and rotary base."""
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "int8"))
def block(p, x, *, dims, int8):
    """One decoder layer over ``x [B, S, d]`` (positions 0..S-1)."""
    H, KV, hd, eps, theta = dims
    B, S, d = x.shape
    G = H // KV
    pos = jnp.arange(S)
    a = p["attn"]
    h = R.rms(x, a["ln"], eps)
    q = _rope(R.mm(h, a["wq"], int8).reshape(B, S, H, hd), pos, theta)
    k = _rope(R.mm(h, a["wk"], int8).reshape(B, S, KV, hd), pos, theta)
    v = R.mm(h, a["wv"], int8).reshape(B, S, KV, hd)
    q = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k, precision=R.HIGHEST)
    s = s / math.sqrt(hd) + jnp.where(pos[None, :] <= pos[:, None], 0.0,
                                      -jnp.inf)
    o = jnp.einsum("bkgqt,btkd->bqkgd", jax.nn.softmax(s, -1), v,
                   precision=R.HIGHEST).reshape(B, S, H * hd)
    x = x + R.mm(o, a["wo"], int8)
    f = p["ffn"]
    h = R.rms(x, f["ln"], eps)
    g = jax.nn.silu(R.mm(h, f["wg"], int8)) * R.mm(h, f["wu"], int8)
    return x + R.mm(g, f["wd"], int8)


@partial(jax.jit, static_argnames=("cfg_items",))
def _layer_weights(root, index, *, cfg_items):
    p = layer(dict(cfg_items), root, index, jnp.bfloat16)
    return jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), p)


def layer_weights(cfg: Dict, root, index: int):
    """Layer ``index``'s weights drawn again from ``root``, in float32."""
    return _layer_weights(root, jnp.int32(index), cfg_items=key_items(cfg))


def embed(table, ids, *, dims):
    """The residual stream ``[B, S, d]`` of token ids ``[B, S]``."""
    return table[ids]


def logits(h, norm, table, *, dims, int8):
    """Logits ``[n, V]`` of hidden rows ``h [n, d]`` through one head."""
    return R.mm(R.rms(h, norm, dims[3]), table.T, int8)


# ----------------------------------------------------------------- counts
def layer_matmul_params(cfg: Dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return d * hq + 2 * d * hkv + hq * d + 3 * d * ff


def layer_params(cfg: Dict) -> int:
    return layer_matmul_params(cfg) + 2 * cfg["hidden_size"]   # two norms


def padded_vocab(cfg: Dict) -> int:
    return (cfg["vocab_size"] + 127) // 128 * 128


def weight_bytes(cfg: Dict, layers: int = None) -> int:
    """Bytes of the weights of ``layers`` decoder layers; with ``layers``
    left out, of the whole model as held (layers, embedding with its
    padding rows, final and exit norms)."""
    if layers is not None:
        return layers * layer_params(cfg) * BYTES
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + padded_vocab(cfg) * d + (1 + cfg["num_exits"]) * d) * BYTES


def kv_bytes_per_position(cfg: Dict, layers: int = None) -> int:
    """Cache bytes one position holds over ``layers`` layers (all when
    left out): a key and a value of every KV head."""
    n = cfg["num_hidden_layers"] if layers is None else layers
    return n * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def decode_cache_bytes(cfg: Dict, layers: int, pos: int) -> int:
    """Cache bytes one token at position ``pos`` reads and writes through
    ``layers`` layers: the keys and values of positions 0..pos."""
    return (pos + 1) * kv_bytes_per_position(cfg, layers)


def head_flops(cfg: Dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def attention_flops(cfg: Dict, layers: int, positions: int) -> int:
    """Scores and weighted sum of one query over ``positions`` keys."""
    return 4 * layers * cfg["num_attention_heads"] * cfg["head_dim"] \
        * positions


def decode_flops(cfg: Dict, layers: int, pos: int, head: bool = True) -> int:
    """One token decoded at cache position ``pos`` through ``layers``
    layers (it attends to ``pos + 1`` positions), with its head."""
    return (2 * layers * layer_matmul_params(cfg)
            + attention_flops(cfg, layers, pos + 1)
            + (head_flops(cfg) if head else 0))


def prefill_flops(cfg: Dict, prompt_len: int, head: bool = True) -> int:
    """A prompt through every layer (causal attention: position ``i``
    attends to ``i + 1`` keys), with the head read at its last position."""
    L, P = cfg["num_hidden_layers"], prompt_len
    return (2 * P * L * layer_matmul_params(cfg)
            + attention_flops(cfg, L, P * (P + 1) // 2)
            + (head_flops(cfg) if head else 0))


def prefill_bytes(cfg: Dict, prompt_len: int) -> int:
    """What a prefill must move at the least: every layer's weights read
    once, the prompt's embedding rows read and its keys and values
    written."""
    L = cfg["num_hidden_layers"]
    return (weight_bytes(cfg, L) + prompt_len * cfg["hidden_size"] * BYTES
            + prompt_len * kv_bytes_per_position(cfg, L))
