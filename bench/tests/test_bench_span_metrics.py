"""Program spans in a trace: device idle time to the innermost covering
span, and the readers of the span and counter metrics."""
import gzip
import importlib.util
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import spans as S
from bench import trace as T

MS = 1_000_000          # nanoseconds
METRICS = Path(__file__).resolve().parents[1] / "metrics"


def _planes(with_spans=True):
    program = [("fleet.event.round", 12 * MS, 85 * MS),
               ("fleet.round", 14 * MS, 82 * MS),
               ("fleet.admit", 16 * MS, 8 * MS),
               ("fleet.prefill", 17 * MS, 2 * MS),
               ("arena.inputs", 26 * MS, 2 * MS),
               ("arena.dispatch", 28 * MS, 1 * MS),
               ("fleet.epilogue", 52 * MS, 38 * MS),
               ("fleet.emit", 60 * MS, 10 * MS),
               ("fleet.emit", 75 * MS, 10 * MS)]
    host = ("/host:CPU", [
        ("python", [(T.WINDOW, 10 * MS, 100 * MS),
                    ("PjitFunction(astep)", 20 * MS, 5 * MS),
                    ("np.asarray", 60 * MS, 30 * MS)]
         + (program if with_spans else []))])
    dev = ("/device:TPU:0", [
        ("XLA Modules", [("jit_astep(12)", 30 * MS, 20 * MS)]),
        ("XLA Ops", [("fusion.1", 5 * MS, 10 * MS),
                     ("fusion.2", 30 * MS, 20 * MS),
                     ("dot.3", 95 * MS, 5 * MS),
                     ("dot.4", 105 * MS, 10 * MS)])])
    return [host, dev]


def test_idle_goes_to_the_innermost_covering_span():
    r = S.reduce(_planes())
    # idle: [15,30] + [50,95] + [100,105] ms of the [10,110] window
    idle = {k: v["idle_s"] * 1e3 for k, v in r["spans"].items()}
    assert idle == pytest.approx({
        "fleet.event.round": 0.0, "fleet.round": 11.0, "fleet.admit": 6.0,
        "fleet.prefill": 2.0, "arena.inputs": 2.0, "arena.dispatch": 1.0,
        "fleet.epilogue": 18.0, "fleet.emit": 20.0})
    assert r["idle_uncovered_s"] == pytest.approx(0.005)   # [100,105]
    assert r["spans"]["fleet.emit"]["count"] == 2
    assert r["spans"]["fleet.emit"]["seconds"] == pytest.approx(0.020)
    assert r["spans"]["fleet.event.round"]["seconds"] == pytest.approx(0.085)
    assert [k for k, _ in r["breakdown"]["idle_by_span"]][:2] == \
        ["fleet.emit", "fleet.epilogue"]
    ctx = {"trace": r}
    shares = {m: _reader(m)(ctx) for m in
              ("idle_epilogue_share", "idle_admit_share", "idle_loop_share")}
    assert shares == pytest.approx({"idle_epilogue_share": 40.0,
                                    "idle_admit_share": 8.0,
                                    "idle_loop_share": 12.0})
    residue = 100.0 * r["idle_uncovered_s"] / r["window_s"]
    assert sum(shares.values()) + residue == \
        pytest.approx(_reader("device_idle_share")(ctx))


def test_spans_leave_the_device_numbers_and_gap_names_as_they_were():
    with_spans, bare = S.reduce(_planes()), T.reduce(_planes(False))
    for key in ("window_s", "busy_s", "modules"):
        assert with_spans[key] == bare[key]
    assert with_spans["breakdown"]["device_ops"] == \
        bare["breakdown"]["device_ops"]
    names = [n for n, _ in bare["breakdown"]["idle_gaps"]]
    assert names == ["host: np.asarray", "host: PjitFunction(astep)",
                     "host: no host event"]
    assert [n for n, _ in with_spans["breakdown"]["idle_gaps"]] == [
        "host: np.asarray in fleet.round",
        "host: PjitFunction(astep) in fleet.round",
        "host: no host event"]          # no span covers [100,105]
    assert [s for _, s in with_spans["breakdown"]["idle_gaps"]] == \
        [s for _, s in bare["breakdown"]["idle_gaps"]]


def test_a_trace_without_spans_reads_nothing():
    r = S.reduce(_planes(False))
    assert r["spans"] == {} and r["breakdown"]["idle_by_span"] == []
    assert r["idle_uncovered_s"] == pytest.approx(0.065)
    for m in ("idle_epilogue_share", "idle_admit_share", "idle_loop_share"):
        assert _reader(m)({"trace": r}) is None
        assert _reader(m)({"trace": T.reduce(_planes(False))}) is None


def test_innermost_stretches():
    segs = S.innermost([("a", 0, 10), ("b", 2, 4), ("c", 4, 6),
                        ("d", 12, 13)])
    assert segs == [(0, 2, "a"), (2, 4, "b"), (4, 6, "c"), (6, 10, "a"),
                    (12, 13, "d")]
    assert S.innermost([]) == []


def test_the_chip_slice_reduces_as_before():
    path = Path(__file__).resolve().parent / "data" / \
        "chat-trace-slice.json.gz"
    planes = [(p, [(ln, [tuple(e) for e in evs]) for ln, evs in lines])
              for p, lines in json.load(gzip.open(path, "rt"))]
    r, bare = S.reduce(planes), T.reduce(planes)
    assert r["busy_s"] == bare["busy_s"]
    assert r["breakdown"]["idle_gaps"] == bare["breakdown"]["idle_gaps"]
    assert r["idle_uncovered_s"] == \
        pytest.approx(r["window_s"] - r["busy_s"], abs=1e-9)


def test_a_slice_recorded_with_spans_on_the_chip():
    """210 ms of the chat cell on a TPU v5e with the profiler attached:
    one arena step, then the host's epilogue and the next step's inputs
    with the chip idle."""
    path = Path(__file__).resolve().parent / "data" / \
        "chat-span-slice.json.gz"
    planes = [(p, [(ln, [tuple(e) for e in evs]) for ln, evs in lines])
              for p, lines in json.load(gzip.open(path, "rt"))]
    r, bare = S.reduce(planes), T.reduce(S.split(planes)[0])
    assert r["busy_s"] == bare["busy_s"]
    assert r["modules"]["jit_astep"]["count"] == 1
    idle = r["window_s"] - r["busy_s"]
    by_span = sum(v["idle_s"] for v in r["spans"].values())
    assert by_span + r["idle_uncovered_s"] == pytest.approx(idle, abs=1e-9)
    assert r["idle_uncovered_s"] < 1e-3
    assert r["spans"]["fleet.emit"]["count"] == 64       # one per slot
    ctx = {"trace": r}
    assert _reader("idle_epilogue_share")(ctx) > \
        0.95 * _reader("device_idle_share")(ctx)
    assert _reader("idle_admit_share")(ctx) == 0.0
    for (name, secs), (was, secs0) in zip(r["breakdown"]["idle_gaps"],
                                          bare["breakdown"]["idle_gaps"]):
        assert secs == secs0
        assert name == was or name.startswith(was + " in fleet.")


def _req(*stamps):
    return SimpleNamespace(arrival_wall=0.0,
                           tokens=SimpleNamespace(stamps=list(stamps)))


def test_counter_readers():
    reqs = [_req(1.0, 2.0, 3.0), _req(4.0, 6.0),
            SimpleNamespace(arrival_wall=None,          # device tier
                            tokens=SimpleNamespace(stamps=[2.0]))]
    prof = {"host_reads": 8, "t0": 0.5, "t1": 5.0,
            "queue_waits": [[1.0, 0.5], [2.0, 0.1], [9.0, 3.0]]}
    ctx = {"requests": reqs, "profile": prof}
    assert _reader("host_reads_per_token")(ctx) == pytest.approx(2.0)
    # waits admitted in [0.5, 5): 0.5 and 0.1; numpy's linear p95
    assert _reader("queue_wait_p95_ms")(ctx) == pytest.approx(480.0)
    for m in ("host_reads_per_token", "queue_wait_p95_ms"):
        assert _reader(m)({"requests": reqs}) is None
        assert _reader(m)({"requests": reqs, "profile": None}) is None
    empty = {"requests": [], "profile": {**prof, "queue_waits": []}}
    assert _reader("host_reads_per_token")(empty) is None
    assert _reader("queue_wait_p95_ms")(empty) is None


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"span_test_{name}", METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
