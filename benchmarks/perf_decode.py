"""Real-decode throughput benchmark: serial vs batched vs arena tokens/s.

One mixed-exit, mixed-geometry real-decode fleet (two tenant classes with
different token budgets => different KV-cache geometries; deadline
demotion on => exits mix mid-stream) runs through each of the engine's
three decode strategies:

* ``serial``  — one compiled call per request per token (pre-PR-9);
* ``batched`` — per-round vmap groups, host-side restack + pad by
  replication, one compiled variant per (exit, batch bucket)  (PR 9);
* ``arena``   — slot-resident decode arena, one masked full-arena call
  per model exit per round, no restacking, no pad rows.

Every path is warmed up with one full run (all compiles land), then the
same engine re-runs the same workload and only that second run is timed —
tokens/s compares steady-state decode, not compile time.  Token streams
are asserted identical across all three paths before anything is
recorded: a throughput number for a wrong decode is not a result.

Results merge into ``BENCH_decode.json`` at the repo root:

    python benchmarks/perf_decode.py            # full cell + gates
    python benchmarks/perf_decode.py --smoke    # CI cell (same shape,
                                                #   shorter horizon)

Gates (``--no-gate`` to measure only):

* arena >= 1.5x batched tokens/s;
* arena compiled variants <= one per model exit;
* zero padded rows on the arena path.
"""
from __future__ import annotations

import argparse
import json
import time
from dataclasses import replace
from pathlib import Path

from repro.sim import (EngineSpec, RouterSpec, ScenarioSpec, Simulation,
                       TopologySpec, WorkloadSpec)

REPO_ROOT = Path(__file__).resolve().parent.parent
BENCH_PATH = REPO_ROOT / "BENCH_decode.json"

PATHS = ("serial", "batched", "arena")
GATE_ARENA_SPEEDUP = 1.5
TIMED_RUNS = 2


def decode_spec(path: str, *, smoke: bool) -> ScenarioSpec:
    """The benchmark cell: a static LTE fleet whose co-located requests mix
    exit points (tight interactive SLO + deadline demotion) and cache
    geometries (two token budgets), decoded via ``path``."""
    from repro.fleet.workload import TenantClass
    tenants = (TenantClass("interactive", slo_s=0.8, max_new_tokens=12,
                           weight=0.5),
               TenantClass("standard", slo_s=3.0, max_new_tokens=24,
                           weight=0.5))
    # edge_capacity 4 with an oversubscribed arrival rate keeps the decode
    # queues saturated, so the arena runs near-full occupancy (its slot
    # count is the pow2 bucket of edge capacity) instead of masking most
    # of its rows — the regime the arena is built for.
    return ScenarioSpec(
        name=f"perf-decode-{path}", seed=3,
        topology=TopologySpec(num_devices=8, num_edges=2, trace="lte",
                              edge_capacity=4, max_edge_slowdown=2.0),
        workload=WorkloadSpec(rate_hz=32.0 if smoke else 48.0,
                              horizon_s=4.0 if smoke else 8.0,
                              device_skew=0.5, prompt_len=6,
                              tenants=tenants),
        router=RouterSpec(name="bandwidth-aware"),
        engine=EngineSpec(real_decode=True, demote_on_deadline=True,
                          batch_decode=(path == "batched"),
                          arena_decode=(path == "arena"),
                          retain_records=False))


def run_cells(*, smoke: bool) -> tuple:
    """One warm-up run per path (compiles land), then ``TIMED_RUNS``
    timed replays with the three paths interleaved — serial, batched,
    arena, serial, ... — so a slow host window degrades every path's
    sample, not one path's entire measurement; each path keeps its
    fastest replay.  Returns the cell dicts plus the token streams for
    the cross-path identity check."""
    scs, st0, walls, metrics = {}, {}, {}, {}
    for path in PATHS:
        sc = Simulation(decode_spec(path, smoke=smoke)).build()
        sc.engine.run(sc.workload)                   # warm-up: compile
        scs[path] = sc
        st0[path] = sc.engine.stepper.cache_stats()
        walls[path] = []
    for _ in range(TIMED_RUNS):
        for path in PATHS:
            sc = scs[path]
            t0 = time.perf_counter()
            metrics[path] = sc.engine.run(sc.workload)
            walls[path].append(time.perf_counter() - t0)
    cells, streams = {}, {}
    for path in PATHS:
        sc = scs[path]
        st1 = sc.engine.stepper.cache_stats()
        wall = min(walls[path])
        tokens = sum(len(r.tokens) for r in sc.workload)
        streams[path] = {r.rid: list(r.tokens) for r in sc.workload}
        cell = {
            "requests": metrics[path].summary()["requests"],
            "tokens": tokens,
            "wall_s": round(wall, 3),
            "tokens_per_s": round(tokens / max(wall, 1e-9), 1),
            "timed_run_compiles":
                st1["jit"]["misses"] - st0[path]["jit"]["misses"],
            "jit_variants": st1["jit"]["variants"],
            # counter deltas span all timed replays; the replays are
            # deterministic, so dividing recovers the per-run counts
            "decode": {
                k: (st1["decode"][k] - st0[path]["decode"][k]) // TIMED_RUNS
                for k in ("batched_calls", "batched_tokens",
                          "padded_rows", "serial_tokens")},
            "arena": {
                k: (st1["arena"][k] - st0[path]["arena"][k]) // TIMED_RUNS
                for k in ("calls", "tokens", "masked_rows", "admits",
                          "evicts", "grows")},
        }
        ar = cell["arena"]
        den = ar["tokens"] + ar["masked_rows"]
        cell["arena"]["occupancy"] = \
            round(ar["tokens"] / den, 4) if den else None
        cells[path] = cell
    return cells, streams


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI cell: same fleet shape, shorter horizon")
    ap.add_argument("--no-gate", action="store_true",
                    help="measure without asserting the gates")
    args = ap.parse_args()

    key = "smoke" if args.smoke else "full"
    print(f"real-decode throughput ({key} cell): "
          f"{', '.join(PATHS)}")
    print(f"\n{'path':>8} {'requests':>9} {'tokens':>8} {'wall':>8} "
          f"{'tokens/s':>9} {'compiles':>9}")
    cells, streams = run_cells(smoke=args.smoke)
    for path in PATHS:
        cell = cells[path]
        print(f"{path:>8} {cell['requests']:>9} {cell['tokens']:>8} "
              f"{cell['wall_s']:>7.2f}s {cell['tokens_per_s']:>9.0f} "
              f"{cell['timed_run_compiles']:>9}")

    # correctness precedes throughput: all three decode strategies must
    # produce the same token streams before their speeds are comparable
    for path in ("batched", "arena"):
        assert streams[path] == streams["serial"], \
            f"{path} token streams diverge from serial"
    print("token streams identical across paths  [ok]")

    arena, batched = cells["arena"], cells["batched"]
    speedup = arena["tokens_per_s"] / max(batched["tokens_per_s"], 1e-9)
    # model exits = the ceiling on compiled arena variants per geometry
    sim = Simulation(decode_spec("arena", smoke=True))
    n_model = sim.build().engine.stepper.n_model
    arena_variants = arena["jit_variants"]["arena"]
    print(f"\narena vs batched: {speedup:.2f}x tokens/s "
          f"(arena variants {arena_variants} <= {n_model} model exits, "
          f"arena padded rows {arena['decode']['padded_rows']})")

    bench = {}
    if BENCH_PATH.exists():
        with open(BENCH_PATH) as f:
            bench = json.load(f)
    bench[key] = {
        "cells": cells,
        "arena_vs_batched_tokens_per_s": round(speedup, 2),
        "recorded_unix": int(time.time()),
    }
    with open(BENCH_PATH, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)
    print(f"wrote {BENCH_PATH}")

    if not args.no_gate:
        assert speedup >= GATE_ARENA_SPEEDUP, (
            f"expected arena >= {GATE_ARENA_SPEEDUP}x batched tokens/s, "
            f"got {speedup:.2f}x")
        assert arena_variants <= n_model, (
            f"{arena_variants} compiled arena variants exceed the "
            f"{n_model} model exits")
        assert arena["decode"]["padded_rows"] == 0, \
            "arena path padded rows"
        assert arena["timed_run_compiles"] == 0, \
            "arena timed run recompiled: warm-up did not cover the run"
        print(f"gates (>= {GATE_ARENA_SPEEDUP}x, <= {n_model} variants, "
              f"0 padded rows)  [ok]")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
