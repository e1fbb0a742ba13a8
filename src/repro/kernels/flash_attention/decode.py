"""Single-query decode attention over arena rows (Pallas TPU kernel).

The decode-specialized sibling of :mod:`.kernel`: one query token per
sequence (S == 1) attending over a *slot-resident* KV arena row — the
layout ``repro.serving.arena.DecodeArena`` keeps caches in.  Arena rows
are padded to a shared bucketed length, so validity is a per-slot
``lengths[b]`` rather than a causal diagonal: key positions at or beyond
the slot's true length are masked to ``NEG_INF``, and whole k-tiles past
the length are block-pruned with ``pl.when`` — the decode twin of the
prefill kernel's block-pruned causality.

Same TPU-native structure as the prefill kernel (DESIGN.md §2): HBM ->
VMEM tiling via BlockSpec, online-softmax accumulators in VMEM scratch,
GQA folded into the k/v index map (query head ``h`` reads kv-head
``h // group``), grid ``(B, H, n_k)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_k: int, n_k: int):
    j = pl.program_id(2)     # k block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[pl.program_id(0)]

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # [1, hd]
        k = k_ref[0, 0].astype(jnp.float32)      # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_old = m_scr[...][:, 0]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_old - m_new)
        l_scr[...] = (l_scr[...][:, 0] * corr + jnp.sum(p, axis=1))[:, None]
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new[:, None]

    # block-pruned padding: k tiles entirely at/beyond the slot's true
    # length hold only arena zero-padding — skip them
    pl.when(j * block_k < length)(_compute)

    @pl.when(j == n_k - 1)
    def _final():
        l = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, *, block_k: int = 128,
                            interpret: bool = False):
    """q: [B, H, 1, hd]; k/v: [B, KV, T, hd]; lengths: [B] int32.

    Returns [B, H, 1, hd].  Row ``b`` attends over ``k[b, :, :lengths[b]]``
    only; the padded tail contributes exactly nothing (a ``lengths[b] == 0``
    row returns zeros)."""
    B, H, S, hd = q.shape
    if S != 1:
        raise ValueError(f"decode kernel is single-query: got S={S}")
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    bk = min(block_k, T)
    assert T % bk == 0, (T, bk)
    nk = T // bk
    scale = 1.0 / math.sqrt(hd)
    lengths = jnp.asarray(lengths, jnp.int32)

    kern = functools.partial(_kernel, scale=scale, block_k=bk, n_k=nk)
    # per-slot lengths ride in SMEM as a scalar-prefetch operand (a rank-1
    # VMEM block of B lengths is not a legal TPU tile); every index map
    # receives the prefetched ref as its trailing argument
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nk),
            in_specs=[
                pl.BlockSpec((1, 1, 1, hd), lambda b, h, j, _: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, j, _, G=G: (b, h // G, j, 0)),
                pl.BlockSpec((1, 1, bk, hd),
                             lambda b, h, j, _, G=G: (b, h // G, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, hd),
                                   lambda b, h, j, _: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, 1), jnp.float32),
                pltpu.VMEM((1, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, hd), q.dtype),
        interpret=interpret,
    )(lengths, q, k, v)
    return out
