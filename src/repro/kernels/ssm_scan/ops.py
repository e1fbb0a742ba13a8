"""jit'd public wrapper for the SSM scan kernel (model layout in/out)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ssm_scan.kernel import ssm_scan_pallas


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _scan(q, k, v, log_w, state, u, *, chunk, interpret):
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, -1)
    o, sT = ssm_scan_pallas(fold(q), fold(k), fold(v), fold(log_w),
                            state.reshape(B * H, dk, dv),
                            None if u is None else jnp.broadcast_to(
                                u[None], (B, H, dk)).reshape(B * H, dk),
                            chunk=chunk, interpret=interpret)
    return (o.reshape(B, H, S, dv).transpose(0, 2, 1, 3),
            sT.reshape(B, H, dk, dv))


def ssm_scan(q, k, v, log_w, state, u=None, *, chunk: int = 16,
             interpret: bool = False):
    """Same contract as ``repro.models.linear_scan.linear_scan``:
    q/k/log_w [B,S,H,dk]; v [B,S,H,dv]; state [B,H,dk,dv]; u [H,dk]|None."""
    return _scan(q, k, v, log_w, state, u, chunk=chunk, interpret=interpret)
