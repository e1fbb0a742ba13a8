"""jit'd public wrapper for the fused exit-head kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.exit_head.kernel import exit_confidence_pallas


@partial(jax.jit, static_argnames=("tile_rows", "tile_v", "interpret"))
def exit_confidence(h, emb, *, tile_rows: int = 256, tile_v: int = 512,
                    interpret: bool = False):
    """h: [B, S, D] exit-normed hidden; emb: [V, D].
    Returns dict(token [B,S] i32, conf [B,S] f32, entropy [B,S] f32) —
    same contract as ``repro.kernels.exit_head.ref.exit_confidence``."""
    B, S, D = h.shape
    tok, conf, ent = exit_confidence_pallas(
        h.reshape(B * S, D), emb, tile_rows=tile_rows, tile_v=tile_v,
        interpret=interpret)
    return {"token": tok.reshape(B, S), "conf": conf.reshape(B, S),
            "entropy": ent.reshape(B, S)}
