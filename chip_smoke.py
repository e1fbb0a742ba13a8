"""Bring-up smoke run: the real-decode fleet path on one TPU chip.

Drives the system's main path once through its normal entry points:
``Simulation`` -> ``FleetEngine(real_decode=True, arena_decode=True)`` ->
jitted prefill -> ``DecodeArena`` rounds -> logits/argmax epilogue, with
granite-3-2b at its published widths (40 layers, d_model 2048, 32/8 heads
of 64, d_ff 8192, vocabulary 49155) and bfloat16 parameters drawn from a
fixed seed.  One edge with 8 slots serves 4 devices: about 16 requests of
128-token prompts, two tenants asking for 32 and 64 new tokens, deadline
demotion on so exits mix.

The workload runs once to warm up (compiles land) and once more; then the
script checks that

* every request completed with its full token count;
* no logits the engine computed held a NaN or an infinity;
* for 2 requests, logits decoded through the KV cache agree with one
  forward pass over the same prompt plus generated tokens;
* for 1 request, the arena step's logits agree with the serial B=1 step's
  (the path the CPU tests pin), both fed the same tokens.

It also replays the workload through the serial decode path and prints
whether its token streams equal the arena's.  Bit-identity across decode
paths is pinned in float32; in bfloat16 the vmapped arena step and the B=1
serial step round differently (on the CPU and on the chip), a near-tie
argmax then flips, and the streams part, so the comparison is reported,
not gated on.

Earlier lines print facts of the run (device, compile and wall seconds,
tokens, peak device memory); the last line is one JSON object.  Exits
non-zero, printing no result, when JAX finds no TPU or a check fails.

    python chip_smoke.py

``run_smoke`` is importable, so tests/test_chip_smoke.py runs the same body
on the CPU at the reduced config.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.fleet.workload import TenantClass  # noqa: E402
from repro.sim import (EngineSpec, PlannerSpec, ScenarioSpec,  # noqa: E402
                       Simulation, TopologySpec, WorkloadSpec)

ARCH = "granite-3-2b"
# relative L2 distance between the two logits vectors at one position.
# Both sides run the same bfloat16 weights and round activations to
# bfloat16 (2^-8 relative) after every matmul, but in different orders
# (one query row at a time against the cache vs all rows at once), so they
# agree only to that rounding accumulated over the layers: a few parts in
# a hundred at most.  A wrong cache position, mask or write gives an
# unrelated logits vector, at a distance near 1.4.
LOGITS_RTOL = 0.1
CHECKED_REQUESTS = 2
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def smoke_spec(*, full_width: bool = True,
               dtype: str = "bfloat16") -> ScenarioSpec:
    """The smoke cell.  Seed, rates and links are picked so every request
    offloads to the edge and deadline demotion mixes exits 1 and 5 within
    the arena (checked without the model in tests/test_chip_smoke.py)."""
    tenants = (TenantClass("interactive", slo_s=0.3, max_new_tokens=32,
                           weight=0.5),
               TenantClass("standard", slo_s=0.6, max_new_tokens=64,
                           weight=0.5))
    return ScenarioSpec(
        name="chip-smoke", seed=4,
        planner=PlannerSpec(arch=ARCH, full_width=full_width),
        topology=TopologySpec(num_devices=4, num_edges=1, edge_capacity=8,
                              lo_mbps=5.0, hi_mbps=50.0),
        workload=WorkloadSpec(rate_hz=4.0, horizon_s=4.0, prompt_len=128,
                              tenants=tenants),
        engine=EngineSpec(real_decode=True, dtype=dtype,
                          demote_on_deadline=True, arena_decode=True))


class _CompileMeter:
    """Backend-compile seconds and persistent-cache hits/misses, read from
    JAX's monitoring events (a cache hit replaces the compile)."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple:
        return self.compile_s, self.hits, self.misses

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


class _FiniteWatch:
    """Stands in for ``model.logits`` on one model instance: every logits
    array the engine computes is folded into one on-device all-finite flag
    (no host sync per call)."""

    def __init__(self, model):
        self.logits = model.logits
        self.calls = 0
        self.finite = jnp.asarray(True)
        self._fold = jax.jit(lambda ok, x: ok & jnp.all(jnp.isfinite(x)))
        model.logits = self

    def __call__(self, params, hidden):
        out = self.logits(params, hidden)
        self.finite = self._fold(self.finite, out)
        self.calls += 1
        return out


def _peak_bytes():
    """The device's peak bytes in use so far (``None`` where the backend
    keeps no such count)."""
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def _streams(workload) -> dict:
    return {r.rid: list(r.tokens) for r in workload}


def _timed_run(engine, workload, meter: _CompileMeter) -> dict:
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    metrics = engine.run(workload)
    wall = time.perf_counter() - t0
    c1, h1, m1 = meter.snapshot()
    return {"wall_s": wall, "tokens": sum(len(r.tokens) for r in workload),
            "compile_s": c1 - c0, "cache_hits": h1 - h0,
            "cache_misses": m1 - m0, "summary": metrics.summary()}


def reference_distance(model, params, req, dtype) -> float:
    """Largest relative L2 distance, over the positions that predicted
    ``req``'s generated tokens, between logits decoded through the KV cache
    (full depth: prefill the prompt, then one cached step per token) and
    logits from one forward pass over prompt plus generated tokens."""
    prompt, gen = req.prompt, req.tokens
    P, n = len(prompt), len(gen)
    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(model.prefill)
        step = jax.jit(lambda p, c, t, pos: model.decode_step(p, c, t, pos)[:2])
        forward = jax.jit(lambda p, t: model.stack.forward(
            model.cfg, p, t, collect_exits=False)[0][-1][1])
        cache = model.init_cache(1, P + n, dtype=dtype)
        h, cache = prefill(params, jnp.asarray(prompt[None]), cache)
        cached = [model.logits(params, h)[0, -1]]
        for i, tok in enumerate(gen[:-1]):
            h, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32),
                            jnp.asarray(P + i, jnp.int32))
            cached.append(model.logits(params, h)[0, -1])
        seq = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
        whole = model.logits(params, forward(params, jnp.asarray(seq[None])))
    a = jnp.stack(cached).astype(jnp.float32)
    b = whole[0, P - 1:].astype(jnp.float32)
    dist = jnp.linalg.norm(a - b, axis=-1) / jnp.linalg.norm(b, axis=-1)
    return float(jnp.max(dist))


def arena_distance(stepper, params, req, dtype, *, slots: int,
                   length: int) -> float:
    """Largest relative L2 distance between full-depth logits of the arena
    step (``req`` alone in a ``slots`` x ``length`` arena) and of the serial
    B=1 step, both prefilled with ``req``'s prompt and fed its generated
    tokens: does the arena compute the serial step's numbers?  ``slots``
    and ``length`` are the hints the engine sizes its arena from."""
    from repro.serving.arena import DecodeArena
    model, full = stepper.model, stepper.n_graph
    prompt, gen = req.prompt, req.tokens
    P = len(prompt)
    cache = model.init_cache(1, P + req.max_new_tokens + 1, dtype=dtype)
    _, cache = stepper.prefill_fn()(params, jnp.asarray(prompt[None]), cache)
    arena = DecodeArena(model, slots=slots, length=length, dtype=dtype)
    slot = arena.admit(req.rid, cache)
    serial_step = stepper.decode_fn(full)
    dists = []
    for i, tok in enumerate(gen[:-1]):
        t, pos = jnp.asarray([[tok]], jnp.int32), P + i
        h, cache = serial_step(params, cache, t, jnp.asarray(pos, jnp.int32))
        [(_, h_all)] = stepper.decode_step_arena(
            params, arena, [(full, slot, t, pos)])
        a = model.logits(params, h_all[slot]).astype(jnp.float32)
        b = model.logits(params, h).astype(jnp.float32)
        dists.append(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    return float(jnp.max(jnp.stack(dists)))


def _identity(got: dict, want: dict) -> tuple:
    """``(held, detail)``: whether two ``{rid: tokens}`` maps are equal."""
    bad = sorted(rid for rid in want if got.get(rid) != want[rid])
    if not bad:
        return True, f"identical on all {len(want)} requests"
    a, b = got.get(bad[0], []), want[bad[0]]
    i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
             min(len(a), len(b)))
    return False, (f"{len(bad)} of {len(want)} requests differ, first "
                   f"rid {bad[0]} at token {i}")


def run_smoke(spec: ScenarioSpec) -> dict:
    """Build the spec, run its workload twice through the arena path, check
    it, then replay it through the serial path.  Returns the run's facts
    (``runs``, ``build_s``, the device's peak bytes after each phase, ...),
    one ``(passed, detail)`` entry per check under ``checks``, and the
    serial-vs-arena comparison as ``identity``."""
    meter = _CompileMeter()
    try:
        sim = Simulation(spec)
        t0 = time.perf_counter()
        sc = sim.build()
        jax.block_until_ready(sc.params)
        build_s = time.perf_counter() - t0
        build_compile_s = meter.snapshot()[0]
        peaks = {"build": _peak_bytes()}
        watch = _FiniteWatch(sc.model)
        engine, workload = sc.engine, sc.workload
        runs = {label: _timed_run(engine, workload, meter)
                for label in ("warmup", "run")}
        peaks["runs"] = _peak_bytes()
        arena = _streams(workload)
        stats = engine.stepper.cache_stats()
        checks = {}
        short = [r.rid for r in workload
                 if len(r.tokens) != r.max_new_tokens]
        done = runs["run"]["summary"]["requests"]
        checks["completion"] = (
            not short and done == len(workload),
            f"{done}/{len(workload)} requests completed, "
            f"{len(short)} short of their token count")
        # the dtype the engine decodes in, for the reference's cache
        dtype = jax.tree_util.tree_leaves(sc.params)[0].dtype
        longest = max(r.max_new_tokens for r in workload)
        picked = [r for r in workload
                  if r.max_new_tokens == longest][:CHECKED_REQUESTS]
        dists = {r.rid: reference_distance(sc.model, sc.params, r, dtype)
                 for r in picked}
        checks["reference_logits"] = (
            len(picked) == CHECKED_REQUESTS
            and all(d <= LOGITS_RTOL for d in dists.values()),
            "max relative L2 distance " + ", ".join(
                f"rid {rid}: {d:.3e}" for rid, d in dists.items())
            + f" (limit {LOGITS_RTOL})")
        # the serial path on the same engine and workload: same plans and
        # exits (virtual timing never depends on the decode path)
        engine.arena_decode = engine.batch_decode = False
        try:
            runs["serial"] = _timed_run(engine, workload, meter)
        finally:
            engine.arena_decode = True
        identity = _identity(arena, _streams(workload))
        # the edge arena's geometry: DecodeArena buckets both the same way
        dist = arena_distance(
            engine.stepper, sc.params, picked[0], dtype,
            slots=spec.topology.edge_capacity,
            length=max(r.prompt_len + r.max_new_tokens + 1
                       for r in workload))
        checks["arena_logits"] = (
            dist <= LOGITS_RTOL,
            f"rid {picked[0].rid}: max relative L2 distance {dist:.3e} "
            f"(limit {LOGITS_RTOL})")
        finite = bool(watch.finite)
        checks["finite_logits"] = (
            finite, f"{watch.calls} logits calls, "
            + ("all finite" if finite else "NaN or inf present"))
        peaks["checks"] = _peak_bytes()
    finally:
        meter.close()
    return {"build_s": build_s, "build_compile_s": build_compile_s,
            "peak_bytes_in_use": peaks, "runs": runs, "checks": checks,
            "identity": identity, "arena_distance": dist,
            "cfg": sc.cfg, "dtype": str(dtype),
            "params": sum(x.size for x in jax.tree_util.tree_leaves(
                sc.params)),
            "requests": len(workload), "arena": stats["arena"],
            "jit_variants": stats["jit"]["variants"]}


def main() -> int:
    from repro.launch.compile_cache import enable_compile_cache
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()     # before the first compile
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}")
    print(f"compile cache: {cache_dir}")
    rep = run_smoke(smoke_spec())
    cfg = rep["cfg"]
    print(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads}x{cfg.hd} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} params={rep['params']} "
          f"dtype={rep['dtype']}")
    print(f"build: wall_s={rep['build_s']:.3f} "
          f"compile_s={rep['build_compile_s']:.3f} "
          f"requests={rep['requests']}")
    for label, r in rep["runs"].items():
        s = r["summary"]
        print(f"run {label}: wall_s={r['wall_s']:.3f} tokens={r['tokens']} "
              f"compile_s={r['compile_s']:.3f} "
              f"cache_hits={r['cache_hits']} "
              f"cache_misses={r['cache_misses']} "
              f"exits={json.dumps(s['exit_histogram'], sort_keys=True)}")
    print(f"arena: {json.dumps(rep['arena'], sort_keys=True)} "
          f"jit_variants={json.dumps(rep['jit_variants'], sort_keys=True)}")
    print("peak_bytes_in_use: " + " ".join(
        f"after_{k}={v}" for k, v in rep["peak_bytes_in_use"].items()))
    ok = True
    for name, (passed, detail) in rep["checks"].items():
        print(f"check {name}: {'ok' if passed else 'FAILED'} ({detail})")
        ok = ok and passed
    held, detail = rep["identity"]
    print(f"token identity serial vs arena: "
          f"{'held' if held else 'BROKEN'} ({detail})")
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
