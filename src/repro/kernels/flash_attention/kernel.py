"""Causal GQA flash-attention Pallas TPU kernel (prefill hot spot).

TPU-native adaptation (DESIGN.md §2): HBM -> VMEM tiling via BlockSpec with
q/k blocks of 128/256 rows (MXU-aligned, multiples of 128 in the contracted
head dim), online-softmax accumulators in VMEM scratch, and *block-pruned
causality*: k-tiles strictly above the diagonal are skipped with ``pl.when``
— the FLOP waste of the masked rectangle in the jnp twin
(``repro.models.layers.flash_attention_jnp``) disappears here.

GQA is expressed in the BlockSpec index map: the k/v block for query head h
is kv-head ``h // group``, so no materialized head repetition.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, n_k: int, causal: bool):
    i = pl.program_id(2)     # q block
    j = pl.program_id(3)     # k block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)      # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_old = m_scr[...][:, 0]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_old - m_new)
        l_scr[...] = (l_scr[...][:, 0] * corr + jnp.sum(p, axis=1))[:, None]
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new[:, None]

    if causal:
        # block-pruned causality: skip k tiles strictly above the diagonal
        pl.when(j * block_k <= i * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(j == n_k - 1)
    def _final():
        l = jnp.maximum(l_scr[...][:, 0], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: [B, H, S, hd]; k/v: [B, KV, T, hd].  Returns [B, H, S, hd]."""
    B, H, S, hd = q.shape
    KV, T = k.shape[1], k.shape[2]
    G = H // KV
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    nq, nk = S // bq, T // bk
    scale = 1.0 / math.sqrt(hd)

    kern = functools.partial(_kernel, scale=scale, block_q=bq, block_k=bk,
                             n_k=nk, causal=causal)
    grid = (B, H, nq, nk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out
