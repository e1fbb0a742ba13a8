"""Operations and bytes that the model's work requires, from the
configuration's shapes alone (bf16 weights and cache: 2 bytes an element).

A decoder layer holds four attention projections and three feed-forward
matrices; a token through ``n`` layers costs two operations per weight it
meets, and attention over ``t`` cached positions costs ``4 * heads *
head_dim * t`` per layer (scores and the weighted sum).  The head is tied
to the embedding: ``2 * hidden * vocab`` per token that is read out.
"""
from __future__ import annotations

from typing import Dict

BYTES = 2          # bfloat16


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


def least_seconds(flops: float, nbytes: float, peaks: Dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_c, t_m = flops / peaks["bf16_flops_per_s"], \
        nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def window_flops(cfg: Dict, reqs, t0: float, t1: float,
                 decode: bool = True) -> int:
    """Model FLOPs a window required: every prompt prefilled in it and
    (with ``decode``) every token decoded in it, each through the layers
    of its exit, with the head read for each token and at the end of each
    prompt."""
    from bench.weights import exit_layers
    from bench.window import window_prefills, window_tokens
    depth = exit_layers(cfg) + [cfg["num_hidden_layers"]]
    flops = sum(prefill_flops(cfg, r.prompt_len)
                for r in window_prefills(reqs, t0, t1))
    if decode:
        flops += sum(decode_flops(cfg, depth[r.exit_point - 1],
                                  r.prompt_len + i)
                     for r, i in window_tokens(reqs, t0, t1))
    return flops
