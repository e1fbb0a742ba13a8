"""Find a traffic file's virtual knee: the highest arrival rate at which
the edge's backlog does not grow.

Arrival rates are on the simulator's virtual clock, and the engine's
virtual timing never depends on the decode path, so the knee is found
once, without the model (``real_decode`` off), and is the same on any
chip.  A rate holds when the edge finishes the whole workload within 5%
of the arrival horizon after the last arrival (an edge that falls behind
drains its backlog after the arrivals stop); the search bisects the rate
on a log scale.

    PYTHONPATH=src python3 bench/knee.py --config granite-3-2b --traffic chat
"""
import argparse
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def drain(cfg, traffic, rate: float, n: int, seed: int = 0):
    """Virtual seconds from the last arrival to the last completion, the
    arrival horizon, and how many requests the device tier served."""
    from repro.fleet.workload import FleetRequest
    from repro.sim import Simulation
    from bench.harness import scenario_spec
    from bench.traffic import make_requests
    t = dict(traffic, requests=n,
             arrivals=dict(traffic["arrivals"], rate_hz=rate))
    sc = Simulation(scenario_spec(cfg, t, real_decode=False)).build()
    reqs = make_requests(t, vocab_size=0, seed=seed, factory=FleetRequest)
    recs = sc.engine.run(reqs).records
    last = max(r.arrival_s for r in reqs)
    return (max(r.finish_s for r in recs) - last, last,
            sum(1 for r in recs if r.edge < 0))


def holds(cfg, traffic, rate, n) -> bool:
    tail, horizon, _ = drain(cfg, traffic, rate, n)
    return tail <= 0.05 * horizon


def knee(cfg, traffic, n: int = 4000, lo: float = 0.01, hi: float = 1000.0,
         steps: int = 20) -> float:
    for _ in range(steps):
        mid = math.sqrt(lo * hi)
        if holds(cfg, traffic, mid, n):
            lo = mid
        else:
            hi = mid
    return lo


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--requests", type=int, default=4000)
    args = ap.parse_args()
    from bench.harness import BENCH, load
    cfg = load(BENCH / "configs" / f"{args.config}.json")
    traffic = load(BENCH / "traffic" / f"{args.traffic}.json")
    k = knee(cfg, traffic, n=args.requests)
    tail, horizon, local = drain(cfg, traffic, k, args.requests)
    print(json.dumps({"config": args.config, "traffic": args.traffic,
                      "knee_hz": k, "requests": args.requests,
                      "horizon_s": horizon, "drain_s": tail,
                      "device_tier_requests": local}))


if __name__ == "__main__":
    main()
