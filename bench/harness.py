"""One benchmark run of one cell, end to end.

Set-up builds the cell through the program's public objects: the
configuration is registered under its own name, ``Simulation`` builds the
edge (one ``FleetEngine`` with ``real_decode``, ``arena_decode`` and
bfloat16), and the weights drawn from the seed (``bench.weights``) replace
the ones the build drew.  A warm-up burst of the cell's own traffic fills
every arena slot with every prompt geometry, so every program the window
drives is compiled (or loaded from the persistent cache) before it opens.

The window is ``FleetEngine.run`` on the seed's workload, cut by the
clock after exactly ``seconds``: the requests stamp their arrival and
each token (``bench.window``), and the first stamp at or after the end
unwinds the engine.  Then the peak device memory is read, the program's
state is freed, and a sample of the requests the window finished is
compared with the float32 reference (``bench.reference``).
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import json
import math
import sys
import time
import types
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench.window import WindowClosed

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
FAMILIES = BENCH / "families"
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
TRACE_SECONDS = 5.0
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class WorkloadExhausted(RuntimeError):
    """The workload ran out before the window closed."""


class _WarmupDone(Exception):
    pass


def load(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(name: str) -> Dict:
    """The cell's ``BENCHMARK.json`` entry with its configuration, traffic
    and check limits loaded from their files."""
    bm = load(ROOT / "BENCHMARK.json")
    cell = next((w for w in bm["workloads"] if w["name"] == name), None)
    if cell is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return {"cell": cell, "benchmark": bm,
            "config": load(BENCH / "configs" / f"{cell['config']}.json"),
            "traffic": load(BENCH / "traffic" / f"{cell['traffic']}.json"),
            "check": load(BENCH / "cells" / f"{name}.json")}


def device_peaks(kind: str) -> Dict:
    table = load(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


@functools.lru_cache(maxsize=None)
def _module(path: Path):
    """The Python file ``path``, loaded once."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    return _module(BENCH / "metrics" / f"{name}.py").read


def family(cfg: Dict):
    """The module of the configuration's model family,
    ``families/<model_type>.py``: the program configuration, the weights'
    layout, the float32 forward and the counts of that block."""
    path = FAMILIES / f"{cfg['model_type']}.py"
    if not path.is_file():
        raise ValueError(f"model_type {cfg['model_type']!r}: no family "
                         f"module {path}")
    return _module(path)


# --------------------------------------------------------------- program
def register_config(cfg: Dict) -> str:
    """Make ``repro.configs.get_config(cfg['name'])`` return the file's
    configuration in this process."""
    import repro.configs as configs
    mc = family(cfg).model_config(cfg)
    if list(mc.exit_layer_indices()) != cfg["exit_after_layers"]:
        raise ValueError(f"the program places exits after layers "
                         f"{mc.exit_layer_indices()}, the file states "
                         f"{cfg['exit_after_layers']}")
    modname = "bench_" + "".join(c if c.isalnum() else "_"
                                 for c in cfg["name"])
    mod = types.ModuleType(f"repro.configs.{modname}")
    mod.CONFIG = mc
    sys.modules[mod.__name__] = mod
    configs._ARCH_MODULES[cfg["name"]] = modname
    return cfg["name"]


def scenario_spec(cfg: Dict, traffic: Dict, *, real_decode: bool,
                  name: str = "bench"):
    """The ``ScenarioSpec`` of a cell: the traffic file's topology and
    planner settings, no workload of its own (the harness passes its
    requests to ``FleetEngine.run``)."""
    from repro.fleet.workload import TenantClass
    from repro.sim import (EngineSpec, PlannerSpec, ScenarioSpec,
                           TopologySpec, WorkloadSpec)
    topo, plan = traffic["topology"], traffic["planner"]
    tenants = tuple(TenantClass(t["name"], slo_s=t["slo_s"],
                                max_new_tokens=1, weight=t["weight"])
                    for t in traffic["tenants"])
    return ScenarioSpec(
        name=name, seed=int(topo["seed"]),
        planner=PlannerSpec(arch=register_config(cfg), full_width=True,
                            latency_req_s=plan["latency_req_s"],
                            input_kb=plan["input_kb"],
                            device_step_s=plan["device_step_s"],
                            edge_step_s=plan["edge_step_s"]),
        topology=TopologySpec(num_devices=topo["devices"],
                              num_edges=topo["edges"],
                              edge_capacity=topo["edge_capacity"],
                              hetero_edges=False, trace=topo["trace"],
                              lo_mbps=topo["lo_mbps"],
                              hi_mbps=topo["hi_mbps"],
                              trace_len=topo["trace_len"]),
        workload=WorkloadSpec(rate_hz=0.0, horizon_s=1.0, tenants=tenants),
        engine=EngineSpec(real_decode=real_decode, dtype="bfloat16",
                          demote_on_deadline=traffic["demote_on_deadline"],
                          arena_decode=True))


def stamped_request_class(clock):
    """A ``FleetRequest`` whose token list stamps each append, whose first
    assignment to an edge stamps its arrival, and which keeps the first
    token the prefill chose (the engine feeds it to the first decode step
    without appending it)."""
    from repro.fleet.workload import FleetRequest
    from bench.window import StampList

    class StampedRequest(FleetRequest):
        arrival_wall: Optional[float] = None
        first_tok = None

        @property
        def tokens(self):
            return self._tokens

        @tokens.setter
        def tokens(self, value):
            self._tokens = StampList(self, value)

        @property
        def edge(self):
            return self._edge

        @edge.setter
        def edge(self, eid):
            if eid >= 0 and self.arrival_wall is None:
                self.arrival_wall = self.clock.now()
            self._edge = eid

        @property
        def next_tok(self):
            return self._next_tok

        @next_tok.setter
        def next_tok(self, tok):
            if tok is not None and self.first_tok is None \
                    and not getattr(self, "_tokens", None):
                self.first_tok = tok
            self._next_tok = tok

    StampedRequest.clock = clock
    return StampedRequest


class CompileMeter:
    """Backend-compile count and seconds and persistent-cache hits and
    misses, from JAX's monitoring events (a cache hit replaces a
    compile)."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> Dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.hits, "cache_misses": self.misses}

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def enable_compile_cache():
    """JAX's persistent compilation cache in the checkout, for programs of
    any compile time, so a second process compiles nothing."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)   # JAX writes into it only
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_bytes() -> int:
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())


# ----------------------------------------------------------------- phases
def build(spec: Dict, seed: int):
    """The cell's engine, serving the seed's weights."""
    import jax
    from repro.sim import Simulation
    from bench import weights
    cfg, traffic = spec["config"], spec["traffic"]
    sim = Simulation(scenario_spec(cfg, traffic, real_decode=True,
                                   name=spec["cell"]["name"]))
    sc = sim.build()
    engine = sc.engine
    want = weights.abstract(cfg)
    have = sc.params                  # what the build drew, key and all
    if jax.tree_util.tree_structure(have) != \
            jax.tree_util.tree_structure(want) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in zip(
                    jax.tree_util.tree_leaves(have),
                    jax.tree_util.tree_leaves(want))):
        raise ValueError("the program's parameter layout differs from the "
                         "one bench/weights.py draws")
    del have
    if sc.graph.num_exits != cfg["num_exits"] + 1:
        raise ValueError("the planner's exits are not the model's heads")
    # the build drew weights of its own: free them before drawing ours
    engine.params = sc.params = None
    gc.collect()
    engine.params = weights.draw(cfg, seed)
    jax.block_until_ready(engine.params)
    return sim, engine


def warm_up(engine, spec: Dict, seed: int, Req, clock, workload) -> Dict:
    """Serve a burst of the cell's traffic until every request of it has
    two tokens: every arena slot, every prompt geometry of ``workload``
    and the arena at its full size go through prefill, admission, the
    arena step and the epilogue once."""
    from bench.traffic import make_requests
    traffic, cfg = spec["traffic"], spec["config"]
    cap = traffic["topology"]["edge_capacity"]
    geoms = {(r.prompt_len, r.max_new_tokens) for r in workload}
    burst = make_requests(traffic, vocab_size=cfg["vocab_size"], seed=seed,
                          factory=Req, count=cap)
    have = {(r.prompt_len, r.max_new_tokens) for r in burst}
    missing = sorted(geoms - have)
    burst = burst[:cap - len(missing)]
    for p, n in missing:
        src = next(r for r in workload if (r.prompt_len, r.max_new_tokens)
                   == (p, n))
        burst.append(Req(rid=len(burst), device=src.device,
                         tenant=src.tenant, slo_s=src.slo_s,
                         max_new_tokens=n, arrival_s=0.0, prompt_len=p,
                         prompt=src.prompt))
    for r in burst:
        r.arrival_s = 0.0
    left = {id(r) for r in burst}

    def on_token(req):
        if len(req.tokens) >= 2:
            left.discard(id(req))
            if not left:
                raise _WarmupDone

    clock.on_token = on_token
    t0 = time.perf_counter()
    try:
        engine.run(burst)
    except _WarmupDone:
        pass
    finally:
        clock.on_token = None
    return {"requests": len(burst), "seconds": time.perf_counter() - t0}


def run_window(engine, workload, seconds: float, clock,
               trace_dir: Optional[Path]) -> Dict:
    """``engine.run`` for exactly ``seconds`` of wall clock.  With
    ``trace_dir``, the profiler traces the last ``TRACE_SECONDS`` of it
    (host runtime events, no Python tracer, which would slow the engine's
    event loop many times over), and is stopped once the window has
    closed: collecting the trace takes tens of seconds, which must not
    land inside the window."""
    import jax
    win = {"seconds": seconds}
    trace = {}
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0

        def start():
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            # made after the profiler starts, or it records nothing
            trace["ann"] = jax.profiler.TraceAnnotation("bench_window")
            trace["ann"].__enter__()
            trace["t0"] = time.perf_counter()
            trace["arena0"] = dict(engine.stepper.cache_stats()["arena"])
    t0 = time.perf_counter()
    if trace_dir is not None:
        if seconds <= TRACE_SECONDS:
            start()
        else:
            clock.mark = t0 + seconds - TRACE_SECONDS
            clock.on_mark = start
    clock.deadline = t0 + seconds
    try:
        engine.run(workload)
    except WindowClosed:
        pass
    else:
        raise WorkloadExhausted(
            f"the {len(workload)} requests were served within "
            f"{time.perf_counter() - t0:.1f} s, before the window closed")
    finally:
        clock.deadline = clock.mark = math.inf
        t_end = time.perf_counter()
        if trace:
            trace.pop("ann").__exit__(None, None, None)
            jax.profiler.stop_trace()
    win.update(t0=t0, t1=t0 + seconds, t_stop=t_end, trace=trace)
    return win


def sample(workload, n: int, seed: int) -> List:
    """Requests the window finished: the longest at each exit it used,
    then others drawn from the seed, ``n`` in all."""
    done = [r for r in workload if r.arrival_wall is not None
            and r.first_tok is not None
            and len(r.tokens) == r.max_new_tokens]
    picked = {}
    for r in sorted(done, key=lambda r: (-r.max_new_tokens, r.rid)):
        picked.setdefault(r.exit_point, r)
    rest = [r for r in done if r not in picked.values()]
    rng = np.random.default_rng([7, int(seed)])
    extra = max(0, n - len(picked))
    chosen = list(picked.values()) + [
        rest[i] for i in sorted(rng.choice(len(rest), min(extra, len(rest)),
                                           replace=False))]
    return sorted(chosen, key=lambda r: r.rid)


def check(spec: Dict, seed: int, chosen: List, device_tier: int) -> Dict:
    """The numbers that decide ``correct``, each with its limit."""
    from bench import reference
    cfg, lim = spec["config"], spec["check"]
    rows = [{"prompt": r.prompt, "first": int(np.asarray(r.first_tok)
                                              .reshape(-1)[0]),
             "tokens": list(r.tokens), "head": r.exit_point}
            for r in chosen]
    gap = reference.served_gaps(cfg, seed, rows)["gap"] if rows \
        else np.zeros(0)
    return {
        "mean_logit_gap": {"value": float(gap.mean()) if len(gap) else None,
                           "limit": lim["mean_logit_gap"], "rule": "<="},
        "tokens_checked": {"value": int(len(gap)),
                           "limit": lim["min_tokens_checked"], "rule": ">="},
        "device_tier_requests": {"value": device_tier, "limit": 0,
                                 "rule": "<="}}


def passed(c: Dict) -> bool:
    v, lim = c["value"], c["limit"]
    return v is not None and (v <= lim if c["rule"] == "<=" else v >= lim)


# -------------------------------------------------------------------- run
def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, spec: Optional[Dict] = None,
             log=print, keep_trace: Optional[Path] = None) -> Dict:
    """One run of cell ``name``; returns the result line's object.
    ``spec`` replaces the files of the cell (the tests' small cells);
    ``keep_trace`` copies the profiler's trace file there."""
    import jax
    from bench import traffic as traffic_mod
    from bench.window import Clock, summary
    spec = spec or cell_spec(name)
    cfg, traffic, cell = spec["config"], spec["traffic"], spec["cell"]
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    log(f"device: {json.dumps(device)}")
    peaks = device_peaks(device["kind"]) if trace else None
    enable_compile_cache()
    meter = CompileMeter()
    try:
        clock = Clock()
        Req = stamped_request_class(clock)
        sim, engine = build(spec, seed)
        workload = traffic_mod.make_requests(
            traffic, vocab_size=cfg["vocab_size"], seed=seed, factory=Req)
        warm = warm_up(engine, spec, seed, Req, clock, workload)
        arena0 = dict(engine.stepper.cache_stats()["arena"])
        m0 = meter.snapshot()
        setup_s = time.perf_counter() - t_process
        trace_dir = None
        if trace:
            trace_dir = TRACE_DIR / f"{name}-{seed}"
            _rmtree(trace_dir)
        win = run_window(engine, workload, seconds, clock, trace_dir)
        m1 = meter.snapshot()
        arena1 = dict(engine.stepper.cache_stats()["arena"])
        peak = peak_bytes()
    finally:
        meter.close()
    device["memory_peak_bytes"] = peak
    t0, t1 = win["t0"], win["t1"]
    edge = [r for r in workload if r.arrival_wall is not None]
    device_tier = sum(1 for r in workload
                      if r.arrival_wall is None and len(r.tokens))
    attempted = sum(1 for r in edge if r.arrival_wall < t1)
    ends = summary(workload, t0, t1)
    exits = {}
    for r in edge:
        if r.tokens:
            exits[r.exit_point] = exits.get(r.exit_point, 0) + 1
    compiles = {k: m1[k] - m0[k] for k in ("compiles", "cache_hits")}
    arena = {k: arena1[k] - arena0[k]
             for k in ("calls", "tokens", "masked_rows", "admits")}
    log(f"setup: setup_s={setup_s:.3f} warmup_s={warm['seconds']:.3f} "
        f"warmup_requests={warm['requests']} "
        f"compile_s={m0['compile_s']:.3f} cache_hits={m0['cache_hits']} "
        f"cache_misses={m0['cache_misses']}")
    log(f"window: seconds={seconds} attempted={attempted} "
        f"tokens={ends['tokens']} overrun_s={win['t_stop'] - t1:.4f}")
    log("latency_ms: " + " ".join(
        f"{k}={v:.3f}" if v is not None else f"{k}=None"
        for k, v in ends.items() if k.endswith("_ms"))
        + f" ttft_n={ends['ttft_n']} itl_n={ends['itl_n']}")
    log(f"exit_histogram: {json.dumps(exits, sort_keys=True)}")
    log(f"device_tier_requests: {device_tier}")
    log(f"compiles_in_window: {compiles['compiles']} "
        f"(cache_hits {compiles['cache_hits']})")
    log(f"arena_in_window: {json.dumps(arena, sort_keys=True)}")
    log(f"peak_bytes_in_use: {peak}")
    chosen = sample(workload, spec["check"]["sample_requests"], seed)
    # free the program's state before the reference takes the chip
    del engine, sim
    for r in workload:
        r.cache = r.next_tok = None
    gc.collect()
    t_check = time.perf_counter()
    checks = check(spec, seed, chosen, device_tier)
    log(f"check: requests={len(chosen)} "
        f"seconds={time.perf_counter() - t_check:.3f}")
    ctx = {"config": cfg, "traffic": traffic, "cell": cell,
           "window": win, "requests": workload, "arena": arena,
           "peaks": peaks, "trace": None}
    result = {"correct": all(passed(c) for c in checks.values()),
              "attempted": attempted, "failed": device_tier}
    if trace:
        from bench import trace as trace_mod
        red = trace_mod.reduce_dir(trace_dir)
        # per-layer metrics read the traced slice only
        ts = win["trace"]["t0"]
        ctx["window"] = {"t0": ts, "t1": t1, "seconds": t1 - ts}
        ctx["arena"] = {k: arena1[k] - win["trace"]["arena0"][k]
                        for k in ("calls", "tokens", "masked_rows")}
        if keep_trace is not None:
            import shutil
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        _rmtree(trace_dir)
        ctx["trace"] = red
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        metrics = {}
        for m in spec["benchmark"]["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = red["breakdown"]
    else:
        values = {"setup_s": setup_s, **ends}
        metrics = {}
        for m in spec["benchmark"]["end_to_end"]:
            if name not in m.get("workloads", [name]):
                continue
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def _rmtree(path: Path):
    import shutil
    shutil.rmtree(path, ignore_errors=True)
