"""Operations and bytes that the model's work requires, from the
configuration's shapes alone.

What a layer, a token or a prompt costs is the configuration's family's
(``bench/families/<model_type>.py``); the counts the readers and tests
call are forwarded to it.  This module keeps the least time of a count on
a chip and the sum over a window.
"""
from __future__ import annotations

from typing import Dict

from bench.harness import family


def layer_matmul_params(cfg: Dict) -> int:
    return family(cfg).layer_matmul_params(cfg)


def weight_bytes(cfg: Dict, layers: int = None) -> int:
    return family(cfg).weight_bytes(cfg, layers)


def kv_bytes_per_position(cfg: Dict, layers: int = None) -> int:
    return family(cfg).kv_bytes_per_position(cfg, layers)


def decode_cache_bytes(cfg: Dict, layers: int, pos: int) -> int:
    return family(cfg).decode_cache_bytes(cfg, layers, pos)


def decode_flops(cfg: Dict, layers: int, pos: int, head: bool = True) -> int:
    return family(cfg).decode_flops(cfg, layers, pos, head)


def prefill_flops(cfg: Dict, prompt_len: int, head: bool = True) -> int:
    return family(cfg).prefill_flops(cfg, prompt_len, head)


def prefill_bytes(cfg: Dict, prompt_len: int) -> int:
    return family(cfg).prefill_bytes(cfg, prompt_len)


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
    fam = family(cfg)
    depth = exit_layers(cfg) + [cfg["num_hidden_layers"]]
    flops = sum(fam.prefill_flops(cfg, r.prompt_len)
                for r in window_prefills(reqs, t0, t1))
    if decode:
        flops += sum(fam.decode_flops(cfg, depth[r.exit_point - 1],
                                      r.prompt_len + i)
                     for r, i in window_tokens(reqs, t0, t1))
    return flops
