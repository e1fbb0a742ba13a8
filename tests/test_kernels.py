"""Per-kernel allclose sweeps against the pure-jnp oracles.  Every call
passes ``interpret=True`` (the kernels default to compiled TPU code), which
executes the Pallas kernel body on CPU; tests/test_tpu_compile.py compiles
the same kernels for a v5e chip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis or skip-stub

from repro.kernels.exit_head import ops as eh_ops
from repro.kernels.exit_head import ref as eh_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.ssm_scan import ops as ss_ops
from repro.kernels.ssm_scan import ref as ss_ref


# ---------------------------------------------------------------- exit head
@pytest.mark.parametrize("B,S,D,V", [
    (2, 4, 64, 1000), (1, 7, 128, 313), (3, 1, 32, 2048), (1, 1, 16, 17),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_exit_head_sweep(B, S, D, V, dtype):
    ks = jax.random.split(jax.random.key(B * S + D + V), 2)
    h = jax.random.normal(ks[0], (B, S, D), dtype)
    emb = jax.random.normal(ks[1], (V, D), dtype)
    got = eh_ops.exit_confidence(h, emb, tile_rows=8, tile_v=128,
                                 interpret=True)
    # the kernel upcasts h/emb to f32 before the dot, so the oracle must do
    # the same — an einsum in bf16 rounds the logits and is the LESS precise
    # of the two, flipping argmax ties and drifting the entropy sum
    want = eh_ref.exit_confidence(h.astype(jnp.float32),
                                  emb.astype(jnp.float32))
    tol = 1e-5 if dtype == jnp.float32 else 1e-4
    assert bool(jnp.all(got["token"] == want["token"]))
    np.testing.assert_allclose(np.asarray(got["conf"]),
                               np.asarray(want["conf"]), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got["entropy"]),
                               np.asarray(want["entropy"]), rtol=tol, atol=tol)


def test_exit_head_confidence_semantics():
    """A peaked logit distribution -> conf near 1, entropy near 0."""
    D, V = 32, 500
    emb = jax.random.normal(jax.random.key(0), (V, D))
    h = 20.0 * emb[42][None, None, :]            # aligned with one row
    got = eh_ops.exit_confidence(h, emb, tile_rows=8, tile_v=128,
                                 interpret=True)
    assert int(got["token"][0, 0]) == 42
    assert float(got["conf"][0, 0]) > 0.9
    assert float(got["entropy"][0, 0]) < 0.5


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,H,KV,S,hd,causal", [
    (1, 4, 2, 256, 64, True), (2, 8, 8, 128, 32, True),
    (1, 4, 1, 256, 64, False), (2, 2, 2, 64, 128, True),
])
def test_flash_attention_sweep(B, H, KV, S, hd, causal):
    ks = jax.random.split(jax.random.key(S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.float32)
    got = fa_ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                                 interpret=True)
    want = fa_ref.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=causal
                            ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    B, H, S, hd = 1, 2, 128, 64
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, hd), jnp.bfloat16)
    got = fa_ops.flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                                 interpret=True)
    want = fa_ref.attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=5e-2, atol=5e-2)


# ------------------------------------------------------ decode attention
@pytest.mark.parametrize("B,H,KV,T,hd", [
    (3, 4, 2, 256, 64), (2, 8, 8, 128, 32), (1, 2, 1, 64, 128),
])
def test_decode_attention_sweep(B, H, KV, T, hd):
    """Single-query arena kernel vs the jnp oracle under ragged per-slot
    lengths (including a zero-length padded slot when B > 2)."""
    ks = jax.random.split(jax.random.key(T + H), 4)
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KV, hd), jnp.float32)
    lengths = jax.random.randint(ks[3], (B,), 1, T + 1)
    if B > 2:
        lengths = lengths.at[B - 1].set(0)     # an empty arena slot
    got = fa_ops.decode_attention(q, k, v, lengths, block_k=64,
                                  interpret=True)
    want = fa_ref.decode_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), lengths).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_bf16():
    B, H, T, hd = 2, 4, 128, 64
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, H, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, H, hd), jnp.bfloat16)
    lengths = jnp.asarray([7, 128], jnp.int32)
    got = fa_ops.decode_attention(q, k, v, lengths, block_k=64,
                                  interpret=True)
    want = fa_ref.decode_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), lengths).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_decode_attention_matches_causal_last_row():
    """Decoding position L-1 with lengths=[L] equals the last row of the
    causal prefill oracle — the kernel prices exactly the step the arena
    path runs."""
    B, H, T, hd = 1, 2, 64, 32
    ks = jax.random.split(jax.random.key(11), 3)
    k = jax.random.normal(ks[1], (B, T, H, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, H, hd), jnp.float32)
    q_full = jax.random.normal(ks[0], (B, T, H, hd), jnp.float32)
    full = fa_ref.attention(q_full.transpose(0, 2, 1, 3),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    got = fa_ops.decode_attention(q_full[:, -1:], k, v,
                                  jnp.asarray([T], jnp.int32), block_k=32,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full[:, -1:]),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- ssm scan
@pytest.mark.parametrize("B,S,H,dk,dv,rwkv", [
    (2, 64, 3, 8, 16, True), (2, 64, 3, 8, 16, False),
    (1, 32, 2, 64, 64, True), (1, 128, 4, 16, 64, False),
])
def test_ssm_scan_sweep(B, S, H, dk, dv, rwkv):
    ks = jax.random.split(jax.random.key(S + dk), 6)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, dk)) * 0.5)
    st0 = jax.random.normal(ks[4], (B, H, dk, dv)) * 0.1
    u = jax.random.normal(ks[5], (H, dk)) * 0.1 if rwkv else None
    o1, s1 = ss_ops.ssm_scan(q, k, v, lw, st0, u=u, chunk=16,
                              interpret=True)
    o2, s2 = ss_ref.ssm_scan(q, k, v, lw, st0, u=u)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=3e-4, atol=3e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=3e-4, atol=3e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 100), nchunk=st.integers(1, 3),
       rwkv=st.booleans(), chunk=st.sampled_from([8, 16]))
def test_property_ssm_scan(seed, nchunk, rwkv, chunk):
    B, H, dk, dv = 1, 2, 8, 8
    S = chunk * nchunk
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    v = jax.random.normal(ks[2], (B, S, H, dv))
    lw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, dk)) * 0.5)
    st0 = jnp.zeros((B, H, dk, dv))
    u = jax.random.normal(ks[5], (H, dk)) * 0.1 if rwkv else None
    o1, s1 = ss_ops.ssm_scan(q, k, v, lw, st0, u=u, chunk=chunk,
                              interpret=True)
    o2, s2 = ss_ref.ssm_scan(q, k, v, lw, st0, u=u)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=5e-4, atol=5e-4)
