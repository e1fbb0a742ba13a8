"""jit'd public wrapper for the flash-attention kernel.

Accepts the model layout [B, S, H, hd] / [B, T, KV, hd] and transposes to the
kernel's [B, H, S, hd] head-major layout.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.flash_attention.decode import decode_attention_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: [B, S, H, hd]; k/v: [B, T, KV, hd] -> [B, S, H, hd]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = flash_attention_pallas(qt, kt, vt, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret)
    return o.transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, lengths, *, block_k: int = 128,
                     interpret: bool = False):
    """Arena-row decode attention in the model layout: q [B, 1, H, hd],
    k/v [B, T, KV, hd] (the slot axis first, as DecodeArena stacks them),
    lengths [B] per-slot true lengths -> [B, 1, H, hd]."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    o = decode_attention_pallas(qt, kt, vt, lengths, block_k=block_k,
                                interpret=interpret)
    return o.transpose(0, 2, 1, 3)
