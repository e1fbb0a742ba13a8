"""Compile-only checks for a TPU v5e chip, without the chip.

The TPU compiler is installed even where no TPU is attached: these tests
describe a ``v5e:2x2`` topology and compile, for one of its chips and with
``interpret=False``, what the served path runs at granite-3-2b's published
widths — each Pallas kernel (the scan at rwkv6-3b's widths), and the
full-depth prefill and arena decode step, whose ``memory_analysis`` must fit
one chip's HBM.  Nothing runs: a pass says the chip's compiler accepts the
program and its memory plan, not that it is fast or right.

The topology is described inside a fixture, never at import, so that only
the test worker that runs this file loads the TPU library.
"""
import os
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import HBM_BYTES
from repro.configs import get_config
from repro.kernels.exit_head import ops as eh_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.ssm_scan import ops as ss_ops
from repro.models import Model
from repro.serving.engine import CoInferenceStepper

GRANITE = get_config("granite-3-2b")
RWKV = get_config("rwkv6-3b")
SLOTS, ARENA_LEN, PROMPT = 8, 256, 128       # chip_smoke.py's arena geometry


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _on_chip(one_chip, tree):
    return jax.tree_util.tree_map(
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)


def _fits_one_chip(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.2f} GiB > one v5e chip"
    return total


# ------------------------------------------------------------------ kernels
def test_flash_attention_prefill_compiles(one_chip):
    H, KV, hd, S = GRANITE.num_heads, GRANITE.num_kv_heads, GRANITE.hd, 2048
    q = _sds(one_chip, (1, S, H, hd), jnp.bfloat16)
    kv = _sds(one_chip, (1, S, KV, hd), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: fa_ops.flash_attention(
        q, k, v, causal=True, interpret=False))
    assert "tpu_custom_call" in fn.lower(q, kv, kv).compile().as_text()


def test_decode_attention_compiles(one_chip):
    H, KV, hd = GRANITE.num_heads, GRANITE.num_kv_heads, GRANITE.hd
    q = _sds(one_chip, (SLOTS, 1, H, hd), jnp.bfloat16)
    kv = _sds(one_chip, (SLOTS, ARENA_LEN, KV, hd), jnp.bfloat16)
    lengths = _sds(one_chip, (SLOTS,), jnp.int32)
    fn = jax.jit(lambda q, k, v, n: fa_ops.decode_attention(
        q, k, v, n, interpret=False))
    assert "tpu_custom_call" in fn.lower(q, kv, kv, lengths).compile() \
        .as_text()


def test_exit_head_compiles(one_chip):
    h = _sds(one_chip, (SLOTS, 1, GRANITE.d_model), jnp.bfloat16)
    emb = _sds(one_chip, (GRANITE.padded_vocab, GRANITE.d_model),
               jnp.bfloat16)
    fn = jax.jit(lambda h, e: eh_ops.exit_confidence(h, e, interpret=False))
    assert "tpu_custom_call" in fn.lower(h, emb).compile().as_text()


@pytest.mark.parametrize("rwkv", [True, False])
def test_ssm_scan_compiles(one_chip, rwkv):
    H, dk, S = RWKV.num_heads, RWKV.hd, 256
    x = _sds(one_chip, (1, S, H, dk), jnp.bfloat16)
    state = _sds(one_chip, (1, H, dk, dk), jnp.float32)
    u = _sds(one_chip, (H, dk), jnp.float32) if rwkv else None
    fn = jax.jit(lambda q, k, v, w, s, u: ss_ops.ssm_scan(
        q, k, v, w, s, u=u, interpret=False))
    assert "tpu_custom_call" in fn.lower(x, x, x, x, state, u).compile() \
        .as_text()


# --------------------------------------------------- served path, full depth
@pytest.fixture(scope="module")
def granite(one_chip):
    """granite-3-2b at full depth as shapes only: bf16 parameters and a
    stepper over the published config (what build_stack makes with
    ``PlannerSpec(full_width=True)``)."""
    model = Model(GRANITE)
    params = _on_chip(one_chip, jax.eval_shape(
        lambda: model.init_params(jax.random.key(0), dtype=jnp.bfloat16)))
    return model, params


def test_granite_prefill_fits_one_chip(one_chip, granite):
    model, params = granite
    stepper = CoInferenceStepper(model, types.SimpleNamespace(num_exits=5),
                                 None)
    cache = _on_chip(one_chip, jax.eval_shape(
        lambda: model.init_cache(1, PROMPT + 64 + 1, dtype=jnp.bfloat16)))
    toks = _sds(one_chip, (1, PROMPT), jnp.int32)
    compiled = stepper.prefill_fn().lower(params, toks, cache).compile()
    assert _fits_one_chip(compiled) > GRANITE.param_count() * 2


def test_granite_arena_decode_step_fits_one_chip(one_chip, granite):
    """The masked full-arena decode step the edge runs every round, at full
    depth (no exit), compiled from the stepper's own constructor."""
    model, params = granite
    stepper = CoInferenceStepper(model, types.SimpleNamespace(num_exits=5),
                                 None)
    arena = types.SimpleNamespace(sig=lambda: ("described", SLOTS))
    fn = stepper.decode_fn_arena(None, arena)
    row = jax.eval_shape(
        lambda: model.init_cache(1, ARENA_LEN, dtype=jnp.bfloat16))
    cache = jax.tree_util.tree_map(
        lambda s: _sds(one_chip, (SLOTS,) + s.shape, s.dtype), row)
    compiled = fn.lower(params, cache,
                        _sds(one_chip, (SLOTS, 1, 1), jnp.int32),
                        _sds(one_chip, (SLOTS,), jnp.int32),
                        _sds(one_chip, (SLOTS,), jnp.bool_)).compile()
    assert _fits_one_chip(compiled) > GRANITE.param_count() * 2
