"""The virtual knee: below it the edge keeps up, above it the backlog
drains only after the arrivals stop."""
import importlib.util
from pathlib import Path

from bench.tests import tiny_cell

_spec = importlib.util.spec_from_file_location(
    "bench_knee", Path(__file__).resolve().parents[1] / "knee.py")
knee = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(knee)


def test_knee_separates_keeping_up_from_falling_behind():
    s = tiny_cell.spec()
    cfg, traffic = s["config"], s["traffic"]
    k = knee.knee(cfg, traffic, n=400, lo=1.0, hi=1000.0, steps=10)
    assert 1.0 < k < 1000.0
    assert knee.holds(cfg, traffic, 0.5 * k, 400)
    assert not knee.holds(cfg, traffic, 2.0 * k, 400)
    assert knee.drain(cfg, traffic, k, 400)[2] == 0   # no device tier
