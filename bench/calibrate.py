"""Readings that a cell's check limits are set from, in one process.

For each seed: the seed's weights go into one engine that is built and
warmed once, a short window of the cell's traffic runs at the cell's own
load, and the same sample a benchmark run takes is compared with the
float32 reference (the program's reading) and read again with the int8
control in the program's place (the control's reading).

    python3 bench/calibrate.py --workload granite-3-2b.chat \
        --seconds 12 --seeds 1 2 3 ... > readings.jsonl

Each line is one seed's readings.  The benchmark's own runs never run
this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=None,
                    help="read the control on the first N seeds only")
    args = ap.parse_args()
    import jax
    import numpy as np
    from bench import harness, reference, traffic, weights
    from bench.window import Clock
    spec = harness.cell_spec(args.workload)
    cfg = spec["config"]
    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    clock = Clock()
    Req = harness.stamped_request_class(clock)
    sim, engine = harness.build(spec, args.seeds[0])
    for k, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        if k:
            engine.params = None
            gc.collect()
            engine.params = weights.draw(cfg, seed)
        work = traffic.make_requests(spec["traffic"],
                                     vocab_size=cfg["vocab_size"],
                                     seed=seed, factory=Req)
        if k == 0:
            harness.warm_up(engine, spec, seed, Req, clock, work)
        harness.run_window(engine, work, args.seconds, clock, None)
        engine.run([])                 # frees the run's arena
        chosen = harness.sample(work, spec["check"]["sample_requests"],
                                seed)
        rows = [{"prompt": r.prompt,
                 "first": int(np.asarray(r.first_tok).reshape(-1)[0]),
                 "tokens": list(r.tokens), "head": r.exit_point}
                for r in chosen]
        out = {"workload": args.workload, "seed": seed,
               "requests": len(rows),
               "exits": sorted({r["head"] for r in rows})}
        if not rows:
            print(json.dumps(out), flush=True)
            continue
        g = reference.served_gaps(cfg, seed, rows)["gap"]
        out["program"] = _stats(g)
        if args.control is None or k < args.control:
            c = reference.control_gaps(cfg, seed, rows)["gap"]
            out["control"] = _stats(c)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


def _stats(g):
    import numpy as np
    return {"mean_gap": float(g.mean()), "max_gap": float(g.max()),
            "positions": int(len(g)),
            "nonzero": int((g > 0).sum()),
            "p99_gap": float(np.percentile(g, 99))}


if __name__ == "__main__":
    sys.exit(main())
