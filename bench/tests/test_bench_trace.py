"""The reduction from a profiler trace to device numbers."""
from pathlib import Path

import pytest

from bench import trace as T

MS = 1_000_000          # nanoseconds


def _planes():
    host = ("/host:CPU", [
        ("python", [(T.WINDOW, 10 * MS, 100 * MS),
                    ("PjitFunction(astep)", 20 * MS, 5 * MS),
                    ("np.asarray", 60 * MS, 30 * MS)])])
    dev = ("/device:TPU:0", [
        ("XLA Modules", [("jit_astep(12)", 5 * MS, 10 * MS),
                         ("jit_astep(12)", 30 * MS, 20 * MS),
                         ("jit_prefill(3)", 95 * MS, 5 * MS)]),
        ("XLA Ops", [("fusion.1", 5 * MS, 10 * MS),     # half before t0
                     ("fusion.2", 30 * MS, 15 * MS),
                     ("fusion.3", 40 * MS, 10 * MS),    # overlaps .2
                     ("dot.4", 95 * MS, 30 * MS)])])    # past the end
    return [host, dev, ("/device:TPU:0 SparseCore", [])]


def test_busy_gaps_and_modules():
    r = T.reduce(_planes())
    assert r["window_s"] == pytest.approx(0.100)
    # union inside [10, 110] ms: [10,15] + [30,50] + [95,110]
    assert r["busy_s"] == pytest.approx(0.040)
    assert r["modules"]["jit_astep"]["count"] == 1      # one starts in it
    assert r["modules"]["jit_prefill"]["seconds"] == pytest.approx(0.005)
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([0.045, 0.015])
    assert gaps[0][0] == "host: np.asarray"
    assert gaps[1][0] == "host: PjitFunction(astep)"


def test_a_trace_without_the_window_is_refused():
    planes = _planes()
    planes[0] = ("/host:CPU", [("python", [])])
    with pytest.raises(ValueError):
        T.reduce(planes)


def test_a_slice_recorded_on_the_chip():
    """140 ms of the chat cell's window on a TPU v5e (one arena step, two
    prefills, twenty admissions): busy time is the union of the op
    intervals, the gaps are the rest of the window."""
    import gzip
    import json
    path = Path(__file__).resolve().parent / "data" / \
        "chat-trace-slice.json.gz"
    planes = [(p, [(ln, [tuple(e) for e in evs]) for ln, evs in lines])
              for p, lines in json.load(gzip.open(path, "rt"))]
    r = T.reduce(planes)
    assert r["window_s"] == pytest.approx(0.140)
    astep = r["modules"]["jit_astep"]
    assert astep["count"] == 1 and 0.05 < astep["seconds"] < 0.1
    assert r["modules"]["jit_prefill"]["count"] == 2
    # the union, counted independently at 1 us resolution
    (w0, w1), = [(s, s + d) for p, ls in planes if p.startswith("/host")
                 for _, evs in ls for n, s, d in evs if n == T.WINDOW]
    dev = dict(next(ls for p, ls in planes if p == "/device:TPU:0"))
    covered = set()
    for _, s, d in dev["XLA Ops"]:
        a, b = max(s, w0), min(s + d, w1)
        covered.update(range(int(a // 1000), int(-(-b // 1000))))
    assert r["busy_s"] == pytest.approx(len(covered) * 1e-6, rel=0.02)
    gaps = r["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    assert all(name.startswith("host: ") for name, _ in gaps)
    assert sum(s for _, s in gaps) <= r["window_s"] - r["busy_s"] + 1e-9
    assert [row[0] for row in r["breakdown"]["device_ops"]][0] == "jit_astep"
