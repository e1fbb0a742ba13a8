"""Fused exit-head Pallas TPU kernel.

The paper's right-sizing knob evaluated at LM scale: deciding whether to exit
at an intermediate head requires argmax token + confidence over a vocab of up
to 202k.  The naive path writes the [T, V] logits to HBM (for llama4 decode:
128 x 202048 x 4B = 103 MB per exit per step) just to reduce them.  This
kernel streams the embedding through VMEM tiles and keeps ONLY the online
accumulators (running max, sum-exp, score-weighted sum, argmax) — logits
never touch HBM, turning the exit decision from memory-bound to
compute-bound.

Math (per row): with running max m, Z = sum e^{s-m}, W = sum s*e^{s-m}:
    conf    = exp(m - (m + log Z)) = 1/Z
    entropy = (m + log Z) - W/Z
    token   = argmax s

Grid: (rows/Tr, V/Tv), vocab tiles innermost (sequential on TPU) so the
accumulators live in VMEM scratch across the sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(h_ref, emb_ref, tok_ref, conf_ref, ent_ref,
            m_scr, z_scr, w_scr, a_scr, *, n_vocab_tiles: int, tile_v: int,
            vocab: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        z_scr[...] = jnp.zeros_like(z_scr)
        w_scr[...] = jnp.zeros_like(w_scr)
        a_scr[...] = jnp.zeros_like(a_scr)

    h = h_ref[...].astype(jnp.float32)           # [Tr, D]
    e = emb_ref[...].astype(jnp.float32)         # [Tv, D]
    s = jax.lax.dot_general(h, e, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Tr, Tv]
    # mask padded vocab tail
    vbase = j * tile_v
    vidx = vbase + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(vidx < vocab, s, NEG_INF)

    m_old = m_scr[...][:, 0]                      # [Tr]
    tile_max = jnp.max(s, axis=1)
    tile_arg = vbase + jnp.argmax(s, axis=1).astype(jnp.int32)
    m_new = jnp.maximum(m_old, tile_max)
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(s - m_new[:, None])
    z_new = z_scr[...][:, 0] * corr + jnp.sum(p, axis=1)
    w_new = w_scr[...][:, 0] * corr + jnp.sum(p * s, axis=1)
    a_old = a_scr[...][:, 0]
    a_new = jnp.where(tile_max > m_old, tile_arg, a_old)

    m_scr[...] = m_new[:, None]
    z_scr[...] = z_new[:, None]
    w_scr[...] = w_new[:, None]
    a_scr[...] = a_new[:, None]

    @pl.when(j == n_vocab_tiles - 1)
    def _final():
        z = jnp.maximum(z_new, 1e-30)
        log_z = m_new + jnp.log(z)
        tok_ref[...] = a_new[:, None]
        conf_ref[...] = (1.0 / z)[:, None]
        ent_ref[...] = (log_z - w_new / z)[:, None]


def exit_confidence_pallas(h2d, emb, *, tile_rows: int = 256,
                           tile_v: int = 512, interpret: bool = False):
    """h2d: [T, D] (already exit-normed); emb: [V, D] tied embedding.
    Returns (token [T] i32, conf [T] f32, entropy [T] f32)."""
    T, D = h2d.shape
    V = emb.shape[0]
    Tr = min(tile_rows, max(8, T))
    padT = (-T) % Tr
    if padT:
        h2d = jnp.pad(h2d, ((0, padT), (0, 0)))
    Tp = T + padT
    Tv = min(tile_v, V)
    padV = (-V) % Tv
    embp = jnp.pad(emb, ((0, padV), (0, 0))) if padV else emb
    nv = (V + padV) // Tv
    grid = (Tp // Tr, nv)

    kern = functools.partial(_kernel, n_vocab_tiles=nv, tile_v=Tv, vocab=V)
    tok, conf, ent = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((Tr, D), lambda i, j: (i, 0)),
            pl.BlockSpec((Tv, D), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((Tr, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((Tr, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((Tr, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Tp, 1), jnp.int32),
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Tp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((Tr, 1), jnp.float32),   # running max
            pltpu.VMEM((Tr, 1), jnp.float32),   # sum exp
            pltpu.VMEM((Tr, 1), jnp.float32),   # score-weighted sum
            pltpu.VMEM((Tr, 1), jnp.int32),     # argmax
        ],
        interpret=interpret,
    )(h2d, embp)
    return tok[:T, 0], conf[:T, 0], ent[:T, 0]
