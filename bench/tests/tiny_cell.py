"""A four-layer granite block and a small chat mix, for running the
harness on the CPU."""
import json
import time
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
ROOT = Path(__file__).resolve().parents[2]


def spec(**check):
    """A cell spec (as ``harness.cell_spec`` builds one) over the tiny
    configuration and traffic."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    return {"cell": {"name": "tiny.chat", "config": "tiny-config",
                     "traffic": "tiny-traffic", "chips": 1},
            "benchmark": bm,
            "config": json.loads((DATA / "tiny-config.json").read_text()),
            "traffic": json.loads((DATA / "tiny-traffic.json").read_text()),
            "check": {"mean_logit_gap": 0.05, "min_tokens_checked": 20,
                      "sample_requests": 4, **check}}


def run(seed=11, seconds=2.0, trace=False, **check):
    """One harness run of the tiny cell, with JAX's persistent cache left
    as the test process has it."""
    from bench import harness
    enable = harness.enable_compile_cache
    harness.enable_compile_cache = lambda: None
    try:
        return harness.run_cell("tiny.chat", seed, seconds, trace,
                                t_process=time.perf_counter(),
                                spec=spec(**check), log=lambda *_: None)
    finally:
        harness.enable_compile_cache = enable
