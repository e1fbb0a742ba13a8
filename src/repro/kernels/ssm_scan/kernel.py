"""Chunked diagonal-decay linear-attention scan Pallas TPU kernel — the
shared recurrence of RWKV-6 and Mamba-2 (see ``repro.models.linear_scan``).

TPU adaptation: a GPU implementation would assign one threadblock per (b, h)
and run warp-level scans; on TPU the natural decomposition is a *sequential
grid* over time chunks with the running state [dk, dv] held in VMEM scratch,
and the intra-chunk part expressed as two MXU matmuls (the [C, C] decay-
weighted attention matrix, then @ v).  Per-chunk cumulative-decay products
are computed in-register (a triangular matmul in log space); MIN_LOG_W
bounds the ratio trick to f32 range for C <= 32.

Grid: (B*H, S/C), chunks innermost.  One kernel instance handles both RWKV
semantics (pre-update output + bonus ``u``) and Mamba-2 (post-update).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MIN_LOG_W = -8.0


def _kernel(q_ref, k_ref, v_ref, lw_ref, s0_ref, u_ref, o_ref, sT_ref,
            state_scr, *, chunk: int, n_chunks: int, rwkv: bool):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    qc = q_ref[0].astype(jnp.float32)            # [C, dk]
    kc = k_ref[0].astype(jnp.float32)
    vc = v_ref[0].astype(jnp.float32)            # [C, dv]
    lw = jnp.maximum(lw_ref[0].astype(jnp.float32), MIN_LOG_W)
    C = chunk
    ti = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)

    # inclusive cumsum over the chunk as a lower-triangular matmul (Mosaic
    # has no cumsum lowering; the MXU does this in one pass)
    logP = jax.lax.dot_general(
        jnp.where(si <= ti, 1.0, 0.0), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)      # [C, dk]
    P = jnp.exp(logP)
    k_ = kc / P
    s = state_scr[...]                           # [dk, dv]

    if rwkv:
        q_ = qc * jnp.exp(logP - lw)             # P_{t-1}
        A = jax.lax.dot_general(q_, k_, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        A = jnp.where(si < ti, A, 0.0)
        u = u_ref[0].astype(jnp.float32)         # [1, dk]
        diag = jnp.sum(qc * u * kc, axis=1)
        A = A + jnp.where(si == ti, diag[:, None], 0.0)
    else:
        q_ = qc * P                              # P_t
        A = jax.lax.dot_general(q_, k_, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        A = jnp.where(si <= ti, A, 0.0)

    intra = jax.lax.dot_general(A, vc, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    inter = jax.lax.dot_general(q_, s, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0] = (intra + inter).astype(o_ref.dtype)

    # state update: S' = diag(P_C) S + sum_s (P_C / P_s) k_s v_s^T; the
    # diagonal scaling is a matmul because P_C is a row and S needs it as
    # a column (static slices only: Mosaic lowers no dynamic_slice)
    last = jax.lax.slice_in_dim(logP, C - 1, C, axis=0)     # [1, dk]
    kP = kc * jnp.exp(last - logP)
    dk = s.shape[0]
    ri = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    decay = jnp.where(ri == ci, jnp.exp(last), 0.0)         # diag(P_C)
    state_scr[...] = jax.lax.dot_general(
        decay, s, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32) + jax.lax.dot_general(
        kP, vc, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == n_chunks - 1)
    def _final():
        sT_ref[0] = state_scr[...]


def ssm_scan_pallas(q, k, v, log_w, state, u=None, *, chunk: int = 16,
                    interpret: bool = False):
    """q/k/lw: [BH, S, dk]; v: [BH, S, dv]; state: [BH, dk, dv] f32;
    u: [BH, dk] or None.  Returns (o [BH, S, dv], final_state)."""
    BH, S, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, S)
    assert S % C == 0, (S, C)
    n = S // C
    rwkv = u is not None
    if u is None:
        u = jnp.zeros((BH, dk), jnp.float32)
    # [BH, 1, dk]: a (1, dk) block spans its array's last two dims, the
    # only legal TPU tile for a row this narrow
    u = u.reshape(BH, 1, dk)

    kern = functools.partial(_kernel, chunk=C, n_chunks=n, rwkv=rwkv)
    o, sT = pl.pallas_call(
        kern,
        grid=(BH, n),
        in_specs=[
            pl.BlockSpec((1, C, dk), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, C, dk), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, C, dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, C, dk), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, dv), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, j: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, dv), v.dtype),
            jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, log_w, state.astype(jnp.float32), u)
    return o, sT
