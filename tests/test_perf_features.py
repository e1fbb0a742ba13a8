"""Regression tests for the §Perf optimizations (EXPERIMENTS.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L


# ------------------------------------------------------- flash custom_vjp
def test_flash_fused_grads_match_dense(rng):
    B, S, H, KV, hd = 2, 128, 4, 2, 32
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    ct = jax.random.normal(ks[3], (B, S, H, hd))

    g_dense = jax.grad(lambda q, k, v: jnp.sum(
        L._sdpa(q, k, v, L.causal_bias(S, S)) * ct), argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(lambda q, k, v: jnp.sum(
        L.flash_attention_fused(q, k, v, True, 32, 32) * ct),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_dense, g_flash):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_flash_fused_noncausal_grads(rng):
    B, S, H, hd = 1, 64, 2, 16
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, H, hd))
    v = jax.random.normal(ks[2], (B, S, H, hd))
    ct = jax.random.normal(ks[3], (B, S, H, hd))
    g1 = jax.grad(lambda q: jnp.sum(L._sdpa(q, k, v, 0.0) * ct))(q)
    g2 = jax.grad(lambda q: jnp.sum(
        L.flash_attention_fused(q, k, v, False, 16, 16) * ct))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------- int8 KV cache
def test_int8_kv_cache_decode_close(rng):
    from repro.configs import get_smoke_config
    from repro.models import Model

    cfg = get_smoke_config("granite-3-2b")
    m = Model(cfg)
    params = m.init_params(rng, dtype=jnp.float32)
    B, S = 2, 12
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    nt = jax.random.randint(jax.random.fold_in(rng, 1), (B, 1), 0, cfg.vocab_size)

    c16 = m.init_cache(B, S + 4, dtype=jnp.float32)
    _, c16 = m.prefill(params, toks, c16)
    h16, _, _ = m.decode_step(params, c16, nt, jnp.asarray(S, jnp.int32))

    c8 = m.init_cache(B, S + 4, dtype=jnp.float32, quant=True)
    _, c8 = m.prefill(params, toks, c8)
    h8, _, _ = m.decode_step(params, c8, nt, jnp.asarray(S, jnp.int32))

    rel = float(jnp.abs(h16 - h8).max() / jnp.abs(h16).max())
    assert rel < 0.05, rel
    # the quantized cache is actually int8
    dts = {str(l.dtype) for l in jax.tree.leaves(c8)}
    assert "int8" in dts


def test_int8_cache_bytes_halve():
    from repro.configs import get_config
    from repro.models import Model

    cfg = get_config("granite-3-8b")
    m = Model(cfg)
    full = jax.eval_shape(lambda: m.init_cache(4, 1024))
    quant = jax.eval_shape(lambda: m.init_cache(4, 1024, quant=True))
    b = lambda t: sum(np.prod(l.shape) * l.dtype.itemsize
                      for l in jax.tree.leaves(t))
    assert b(quant) < 0.6 * b(full)


# ------------------------------------------------------- padded heads
def test_padded_heads_zero_grad(rng):
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models import Model

    cfg = dataclasses.replace(get_smoke_config("granite-3-2b"),
                              num_heads=20, num_kv_heads=4, head_dim=16,
                              d_model=64)
    assert cfg.padded_heads == 32   # 4 kv-groups x 8 (first multiple: 4*Gp%16==0)
    m = Model(cfg)
    params = m.init_params(rng, dtype=jnp.float32)
    batch = {"tokens": jax.random.randint(rng, (2, 17), 0, cfg.vocab_size)}
    loss, _ = m.loss(params, batch, remat=False)
    assert bool(jnp.isfinite(loss))
    g = jax.grad(lambda p: m.loss(p, batch, remat=False)[0])(params)
    wo_g = g["segments"][0]["attn"]["wo"]
    hd, Hp = cfg.hd, cfg.padded_heads
    Gp, G = Hp // 4, 20 // 4
    pad_rows = np.repeat((np.arange(Hp) % Gp) >= G, hd)
    assert float(jnp.abs(wo_g[:, pad_rows, :]).max()) == 0.0
    assert float(jnp.abs(wo_g[:, ~pad_rows, :]).max()) > 0.0


def test_padded_heads_noop_when_divisible():
    from repro.configs import get_config
    assert get_config("granite-3-2b").padded_heads == 32
    assert get_config("llama4-maverick-400b-a17b").padded_heads == 48
    assert get_config("starcoder2-15b").padded_heads == 48
    from repro.configs import get_smoke_config
    assert get_smoke_config("granite-3-2b").padded_heads == 4  # < axis: no pad


# ------------------------------------------------------- HLO cost walker
def test_hlo_cost_walker_exact_on_matmul_and_scan():
    import os
    import subprocess
    import sys

    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_cost import walk_costs
from jax.sharding import AxisType
mesh = jax.make_mesh((2,4), ("data","model"), axis_types=(AxisType.Auto,) * 2)
x_sh = NamedSharding(mesh, P("data", None))
w_sh = NamedSharding(mesh, P("data","model"))
def scanned(x, ws):
    def body(c, w): return c @ w, None
    return jax.lax.scan(body, x, ws)[0]
ws_sh = NamedSharding(mesh, P(None, "data","model"))
g = jax.jit(scanned, in_shardings=(x_sh, ws_sh), out_shardings=x_sh)
co = g.lower(jax.ShapeDtypeStruct((64,128), jnp.float32),
             jax.ShapeDtypeStruct((5,128,128), jnp.float32)).compile()
fl, _ = walk_costs(co.as_text())
print(json.dumps({"flops": fl, "expect": 5*2*64*128*128/8}))
"""
    env = dict(os.environ, PYTHONPATH=src)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert abs(r["flops"] - r["expect"]) / r["expect"] < 0.02


# ------------------------------------------------------- collective parser
def test_link_bytes_model():
    from repro.launch.dryrun import _link_bytes
    # all-gather of result 1600 over group 4: each device receives 3/4
    assert _link_bytes("all-gather", 1600, 4) == pytest.approx(1200)
    assert _link_bytes("all-reduce", 1000, 4) == pytest.approx(1500)
    assert _link_bytes("reduce-scatter", 100, 4) == pytest.approx(300)
