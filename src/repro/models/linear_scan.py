"""Diagonal-decay linear-attention scan — the shared recurrence of RWKV-6 and
Mamba-2 (SSD):

    S_t = diag(w_t) @ S_{t-1} + k_t v_t^T          (state:  [dk, dv])
    o_t = q_t @ (S_{t-1} + diag(u) k_t v_t^T)      (rwkv: pre-update + bonus)
    o_t = q_t @ S_t                                 (mamba2: post-update)

Two implementations with identical semantics:
  * ``scan_sequential`` — plain ``lax.scan`` over time (decode / oracle).
  * ``scan_chunked``    — chunk-parallel ratio-trick formulation (train /
    prefill); per chunk the intra-chunk part is a masked matmul, the
    inter-chunk part carries the state.  This is the jnp twin of the Pallas
    kernel in ``repro.kernels.ssm_scan``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# clamp on per-step log-decay: keeps the chunk ratio trick inside f32 range
MIN_LOG_W = -8.0


def scan_sequential(q, k, v, log_w, state, u=None):
    """q/k/log_w: [B,S,H,dk]; v: [B,S,H,dv]; state: [B,H,dk,dv] (f32).

    Returns (o [B,S,H,dv], final_state).  ``u`` (per-head bonus, [H,dk])
    switches to RWKV semantics (output from pre-update state + bonus)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    w = jnp.exp(jnp.maximum(log_w.astype(jnp.float32), MIN_LOG_W))

    def step(s, inp):
        qt, kt, vt, wt = inp  # [B,H,dk], [B,H,dk], [B,H,dv], [B,H,dk]
        kv = kt[..., :, None] * vt[..., None, :]          # [B,H,dk,dv]
        if u is not None:
            o = jnp.einsum("bhk,bhkv->bhv", qt, s + u[None, :, :, None] * kv)
            s = wt[..., None] * s + kv
        else:
            s = wt[..., None] * s + kv
            o = jnp.einsum("bhk,bhkv->bhv", qt, s)
        return s, o

    xs = (qf.transpose(1, 0, 2, 3), kf.transpose(1, 0, 2, 3),
          vf.transpose(1, 0, 2, 3), w.transpose(1, 0, 2, 3))
    state, o = jax.lax.scan(step, state.astype(jnp.float32), xs)
    return o.transpose(1, 0, 2, 3).astype(v.dtype), state


def scan_chunked(q, k, v, log_w, state, u=None, chunk: int = 16):
    """Chunk-parallel twin of :func:`scan_sequential` (same outputs).

    Within a chunk of length C the output decomposes into
      inter: (q_t * P_{t-1}) @ S_chunk_in
      intra: [(q_t * P_{t-1}) @ (k_s / P_s)^T masked s<t  (+ diag bonus)] @ v
    where P_t = prod_{tau<=t} w_tau.  MIN_LOG_W bounds P so k/P stays finite
    in f32 for C <= 32.
    """
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    assert S % chunk == 0, (S, chunk)
    C = chunk
    N = S // C
    qf = q.astype(jnp.float32).reshape(B, N, C, H, dk)
    kf = k.astype(jnp.float32).reshape(B, N, C, H, dk)
    vf = v.astype(jnp.float32).reshape(B, N, C, H, dv)
    lw = jnp.maximum(log_w.astype(jnp.float32), MIN_LOG_W).reshape(B, N, C, H, dk)

    def chunk_step(s, inp):
        qc, kc, vc, lwc = inp                       # [B,C,H,*]
        logP = jnp.cumsum(lwc, axis=1)              # [B,C,H,dk], log P_t
        P = jnp.exp(logP)
        k_ = kc / P
        if u is not None:
            # rwkv: pre-update state -> coeff P_{t-1}, strict mask, diag bonus u
            q_ = qc * jnp.exp(logP - lwc)
            A = jnp.einsum("bthk,bshk->bhts", q_, k_)
            A = A * jnp.tril(jnp.ones((C, C), jnp.float32), -1)[None, None]
            diag = jnp.einsum("bthk,hk,bthk->bth", qc, u, kc)  # [B,C,H]
            A = A + jnp.eye(C, dtype=jnp.float32)[None, None] * diag.transpose(0, 2, 1)[:, :, :, None]
        else:
            # mamba2: post-update state -> coeff P_t, inclusive mask
            q_ = qc * P
            A = jnp.einsum("bthk,bshk->bhts", q_, k_)
            A = A * jnp.tril(jnp.ones((C, C), jnp.float32))[None, None]
        intra = jnp.einsum("bhts,bshv->bthv", A, vc)
        inter = jnp.einsum("bthk,bhkv->bthv", q_, s)
        # state update: S' = diag(P_C) S + sum_s diag(P_C / P_s) k_s v_s
        kP = kc * jnp.exp(logP[:, -1:, :, :] - logP)
        s = P[:, -1][..., None] * s + jnp.einsum("bshk,bshv->bhkv", kP, vc)
        return s, intra + inter

    xs = tuple(t.transpose(1, 0, 2, 3, 4) for t in (qf, kf, vf, lw))
    # remat the chunk body: autodiff then saves only (state, chunk inputs)
    # per step instead of every intra-chunk intermediate (logP, k/P, A, ...)
    # — the dominant HBM-residual traffic of SSM training
    # (EXPERIMENTS.md §Perf C2)
    state, o = jax.lax.scan(jax.checkpoint(chunk_step),
                            state.astype(jnp.float32), xs)
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, S, H, dv)
    return o.astype(v.dtype), state


def linear_scan(q, k, v, log_w, state, u=None, *, mode: str = "auto",
                chunk: int = 16, use_kernel: bool = False):
    """Dispatch: sequential for short/decode, chunked for long sequences,
    Pallas kernel when ``use_kernel`` (compiled for TPU)."""
    if use_kernel:
        from repro.kernels.ssm_scan import ops as ssm_ops
        return ssm_ops.ssm_scan(q, k, v, log_w, state, u=u, chunk=chunk)
    S = q.shape[1]
    if mode == "sequential" or (mode == "auto" and (S < chunk or S % chunk)):
        return scan_sequential(q, k, v, log_w, state, u=u)
    return scan_chunked(q, k, v, log_w, state, u=u, chunk=chunk)
