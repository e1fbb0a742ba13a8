"""The plain reference: the dense GQA decoder in float32 ``jax.numpy``.

It imports nothing of the program.  It draws every layer's weights again
from the seed (``weights.layer``), upcasts them to float32 and runs the
served sequences through the layers one at a time, in blocks of rows, at
``Precision.HIGHEST``, so it fits beside nothing else on one chip.

The block is the program's block, which departs from the published
granite model in the same way (the configuration files list how): RMS
norm before attention and before a SwiGLU feed-forward, rotary positions
on the two halves of each head, softmax scaled by ``1/sqrt(head_dim)``,
no embedding, attention, residual or logits multipliers, and a head tied
to the embedding after the final norm or after an exit head's own norm.

``served_gaps`` is the comparison that decides ``correct``.  For each served
position it reads the reference's logits and returns by how much the
served token's logit lies below the reference's best, so a token that the
reference also puts first reads 0.  With ``int8=True`` the same forward
runs every matrix product in int8 (weights per output column, activations
per row, int32 accumulation): the lower-precision control, whose first
choices are read under the float32 logits in the same way.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8), scale


def _mm(a, w, int8: bool):
    """``a [..., k] @ w [k, n]`` in float32, or in int8 for the control."""
    if not int8:
        return jnp.matmul(a, w, precision=HIGHEST)
    qa, sa = _q8(a, -1)
    qw, sw = _q8(w, 0)
    acc = jnp.matmul(qa, qw, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sa * sw


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs          # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("dims", "int8"))
def _layer(p, x, *, dims, int8):
    """One decoder layer over ``x [B, S, d]`` (positions 0..S-1)."""
    H, KV, hd, eps, theta = dims
    B, S, d = x.shape
    G = H // KV
    pos = jnp.arange(S)
    a = p["attn"]
    h = _rms(x, a["ln"], eps)
    q = _rope(_mm(h, a["wq"], int8).reshape(B, S, H, hd), pos, theta)
    k = _rope(_mm(h, a["wk"], int8).reshape(B, S, KV, hd), pos, theta)
    v = _mm(h, a["wv"], int8).reshape(B, S, KV, hd)
    q = q.reshape(B, S, KV, G, hd)
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k, precision=HIGHEST)
    s = s / math.sqrt(hd) + jnp.where(pos[None, :] <= pos[:, None], 0.0,
                                      -jnp.inf)
    o = jnp.einsum("bkgqt,btkd->bqkgd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST).reshape(B, S, H * hd)
    x = x + _mm(o, a["wo"], int8)
    f = p["ffn"]
    h = _rms(x, f["ln"], eps)
    g = jax.nn.silu(_mm(h, f["wg"], int8)) * _mm(h, f["wu"], int8)
    return x + _mm(g, f["wd"], int8)


@partial(jax.jit, static_argnames=("cfg_items",))
def _layer_weights(root, index, *, cfg_items):
    p = W.layer(dict(cfg_items), root, index, jnp.bfloat16)
    return jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), p)


@partial(jax.jit, static_argnames=("int8", "eps"))
def _head(h, norm, table, *, int8, eps):
    """Logits ``[n, V]`` of hidden rows ``h [n, d]`` through one head."""
    return _mm(_rms(h, norm, eps), table.T, int8)


def _rows(requests: Sequence[Dict]):
    """Token ids ``[R, S]`` (zero-padded) and the positions whose logits
    are read: per read, its row, position, target and head.  Position P-1
    (the prefill's last) is read at the final head, the positions after it
    at the request's own head."""
    seqs, reads = [], []
    for i, r in enumerate(requests):
        prompt = [int(t) for t in r["prompt"]]
        served = [int(r["first"])] + [int(t) for t in r["tokens"]]
        seqs.append(prompt + served[:-1])
        P = len(prompt)
        reads += [(i, P - 1 + j, tok, None if j == 0 else r["head"])
                  for j, tok in enumerate(served)]
    S = max(map(len, seqs))
    ids = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    return ids, reads


def hidden_rows(cfg: Dict, seed: int, requests: Sequence[Dict], *,
                int8: bool = False, block: int = 4) -> Dict[str, np.ndarray]:
    """Run the served sequences through the layers and keep, for every
    position whose next token was served, the residual stream at the depth
    of the head that chose it.  ``requests`` rows hold ``prompt``,
    ``first`` (the prefill's token), ``tokens`` (the decoded ones) and
    ``head`` (1-based exit head of the decoded tokens; ``num_exits + 1`` is
    the final head).  Returns ``hidden [N, d]`` (float32), ``head [N]``,
    ``target [N]`` (the served token) and ``request [N]`` (row index)."""
    L, V = cfg["num_hidden_layers"], cfg["vocab_size"]
    depth = W.exit_layers(cfg) + [L]
    final = len(depth)
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]))
    root = W.root_key(seed)
    table = W.embedding(cfg, root, jnp.bfloat16)[:V].astype(jnp.float32)
    ids, reads = _rows(requests)
    # whole blocks only, so one compiled layer serves every block
    ids = np.concatenate([ids, np.zeros((-len(ids) % block, ids.shape[1]),
                                        np.int32)])
    x = [table[jnp.asarray(ids[a:a + block])]
         for a in range(0, len(ids), block)]
    del table
    heads = np.asarray([final if h is None else h for _, _, _, h in reads])
    rows = np.asarray([r for r, _, _, _ in reads])
    pos = np.asarray([p for _, p, _, _ in reads])
    hidden = np.zeros((len(reads), cfg["hidden_size"]), np.float32)
    items = W.key_items(cfg)
    for li in range(L):
        p = _layer_weights(root, jnp.int32(li), cfg_items=items)
        x = [_layer(p, xb, dims=dims, int8=int8) for xb in x]
        at = np.asarray([depth[h - 1] == li + 1 for h in heads])
        if at.any():
            host = np.concatenate([np.asarray(xb) for xb in x])
            hidden[at] = host[rows[at], pos[at]]
    return {"hidden": hidden, "head": heads,
            "target": np.asarray([t for _, _, t, _ in reads]),
            "request": rows}


@partial(jax.jit, static_argnames=("int8", "eps"))
def _scores(h, head, choices, norms, table, *, int8, eps):
    logits = _mm(_rms(h, norms[head - 1], eps), table.T, int8)
    return (logits.max(-1), logits.argmax(-1),
            jnp.take_along_axis(logits, choices, -1))


def head_scores(cfg: Dict, seed: int, rows: Dict[str, np.ndarray], *,
                int8: bool = False, choices=None,
                chunk: int = 256) -> Dict[str, np.ndarray]:
    """The logits of ``rows`` (from :func:`hidden_rows`) through each row's
    head, reduced on the device: per row the best logit, the token that
    has it, and the logits of the tokens in ``choices [N, k]`` (default:
    the served token)."""
    root = W.root_key(seed)
    V, n_exit = cfg["vocab_size"], cfg["num_exits"]
    table = W.embedding(cfg, root, jnp.bfloat16)[:V].astype(jnp.float32)
    norms = jnp.stack([W.exit_norm(cfg, root, e, jnp.bfloat16)
                       for e in range(n_exit)]
                      + [W.final_norm(cfg, root, jnp.bfloat16)]
                      ).astype(jnp.float32)
    h, head = rows["hidden"], rows["head"]
    if choices is None:
        choices = rows["target"][:, None]
    n = len(h)
    pad = -n % chunk
    h = np.concatenate([h, np.zeros((pad, h.shape[1]), np.float32)])
    head = np.concatenate([head, np.ones(pad, head.dtype)])
    choices = np.concatenate([choices, np.zeros((pad, choices.shape[1]),
                                                choices.dtype)])
    out = [_scores(jnp.asarray(h[a:a + chunk]), jnp.asarray(head[a:a + chunk]),
                   jnp.asarray(choices[a:a + chunk], jnp.int32), norms, table,
                   int8=int8, eps=float(cfg["rms_norm_eps"]))
           for a in range(0, len(h), chunk)]
    best, arg, at = (np.concatenate([np.asarray(o[i]) for o in out])[:n]
                     for i in range(3))
    return {"best": best, "argmax": arg, "at": at}


def served_gaps(cfg: Dict, seed: int, requests: Sequence[Dict]
                ) -> Dict[str, np.ndarray]:
    """The comparison that decides ``correct``: per served position, the
    reference's best logit less its logit of the served token."""
    rows = hidden_rows(cfg, seed, requests)
    s = head_scores(cfg, seed, rows)
    return {"gap": s["best"] - s["at"][:, 0], "request": rows["request"]}


def control_gaps(cfg: Dict, seed: int, requests: Sequence[Dict]
                 ) -> Dict[str, np.ndarray]:
    """The control: the reference computed in int8 chooses a token at each
    served position (the program's own tokens fed in), and the gap of that
    choice is read under the float32 logits, as :func:`served_gaps` reads
    the program's."""
    ctrl = head_scores(cfg, seed, hidden_rows(cfg, seed, requests,
                                              int8=True), int8=True)
    ref_rows = hidden_rows(cfg, seed, requests)
    s = head_scores(cfg, seed, ref_rows, choices=ctrl["argmax"][:, None])
    return {"gap": s["best"] - s["at"][:, 0], "request": ref_rows["request"]}
