"""repro.obs: tracing, timelines, self-profiling — and the determinism
contract they ride on.

The load-bearing property: observers are *read-only* with respect to the
simulation.  Attaching a Tracer/Timeline/SimProfiler never schedules
simulation events (timeline "obs" ticks excepted — and those never mutate
state), consumes RNG, or reorders the heap, so ``summary()`` and the
``handover_log`` are bit-identical with observers on or off.  That is
asserted here deterministically on the smoke scenarios and (with
hypothesis installed) fuzzed over fleet shapes.

Also covered: registry instruments, the schema-complete zero-request
summary, structural trace well-formedness (non-negative durations, spans
nested within their request's lifetime, monotone per-track timestamps,
balanced async pairs), timeline export/load round-trips, the profiler
report, and the ``repro.sim --trace`` / ``python -m repro.obs`` CLIs.
"""
import json
from dataclasses import replace

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.fleet.metrics import FleetMetrics
from repro.obs import (EDGE_GAUGES, MetricsRegistry, SimProfiler, Timeline,
                       Tracer, load_timeline, load_trace, validate_trace)
from repro.sim import (MobilitySpec, PlannerSpec, RouterSpec, ScenarioSpec,
                       Simulation, TopologySpec, WorkloadSpec, get_scenario)

# ---------------------------------------------------------------- registry


def test_registry_instruments():
    r = MetricsRegistry()
    c = r.counter("n")
    c.inc()
    c.inc(3)
    assert c.value == 4
    g = r.gauge("depth")
    g.set(2.5)
    assert g.value == 2.5
    f = r.family("exits")
    f.inc(3)
    f.inc(1, 2)
    f.inc(3)
    assert f.as_dict() == {1: 2, 3: 2}          # sorted label order
    assert f.get(1) == 2 and f.get(9) == 0
    assert 3 in f and 9 not in f and len(f) == 2


def test_registry_histogram_matches_numpy():
    r = MetricsRegistry()
    h = r.histogram("lat")
    vals = [0.3, 1.7, 0.2, 5.0, 0.9]
    for v in vals:
        h.observe(v)
    # bit-identical to the pre-registry list math (the summary() contract)
    assert h.percentile(95) == float(np.percentile(np.array(vals), 95))
    assert h.mean() == float(np.mean(np.array(vals)))
    empty = r.histogram("unused")
    assert empty.percentile(50) is None and empty.mean() is None


def test_registry_get_or_create_and_kind_clash():
    r = MetricsRegistry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(ValueError, match="already registered"):
        r.histogram("x")
    r.counter("x").inc()
    r.histogram("h").observe(1.0)
    snap = r.snapshot()
    assert snap["x"] == 1
    assert snap["h"]["count"] == 1
    assert "x" in r and "h" in r.names()


# ------------------------------------------------- schema-complete summary


def test_empty_summary_schema_complete():
    """Zero completed requests must not change the summary schema: same
    keys, zero/empty values, None for undefined statistics."""
    empty = FleetMetrics(num_edges=3).summary()
    populated = Simulation(_small_mobility_spec()).run().summary()
    assert set(empty) == set(populated)
    assert empty["requests"] == 0
    assert empty["slo_attainment"] == 0.0
    assert empty["p50_latency_s"] is None
    assert empty["p95_latency_s"] is None
    assert empty["mean_queue_delay_s"] is None
    assert empty["handover_slo"] is None
    assert empty["exit_histogram"] == {}
    assert empty["slo_by_tenant"] == {}
    assert empty["edge_utilization"] == {0: 0.0, 1: 0.0, 2: 0.0}
    json.dumps(empty)                           # still JSON-serializable


def test_summary_without_requests_keeps_observed_aggregates():
    """Non-request aggregates (handovers, backbone traffic) still report
    what was observed even when no request completed."""
    m = FleetMetrics(num_edges=2)
    m.add_transfer(0, 1, 500_000)
    m.add_handover(0, 1, 500_000, t_s=1.5)
    s = m.summary()
    assert s["requests"] == 0
    assert s["handovers"] == 1
    assert s["backbone_mb"] == 0.5
    assert s["migrated_mb"] == 0.5


# ------------------------------------------------------ observer neutrality


def _small_mobility_spec(seed=7):
    return ScenarioSpec(
        name="obs-mobility", seed=seed,
        planner=PlannerSpec(result_kb=4.0),
        topology=TopologySpec(kind="mobile", num_devices=10, num_edges=3,
                              speed=0.5, horizon_s=40.0, floor_mbps=0.1,
                              noise_sigma=0.08),
        workload=WorkloadSpec(rate_hz=6.0, horizon_s=8.0),
        router=RouterSpec(name="nearest"),
        mobility=MobilitySpec(policy="bocd"))


def _run_observed(spec, tmp_path, tag):
    traced = replace(spec, engine=replace(
        spec.engine, trace=str(tmp_path / f"{tag}.json"),
        timeline=str(tmp_path / f"{tag}.jsonl")))
    sim = Simulation(traced)
    m = sim.run()
    return sim, m


@pytest.mark.parametrize("scenario", ["smoke-lm", "smoke-mobility"])
def test_observer_neutrality_smoke(scenario, tmp_path):
    """The tentpole contract on the canonical scenarios: summaries AND the
    handover log are bit-identical with the tracer+timeline attached."""
    spec = get_scenario(scenario)
    base = Simulation(spec).run()
    sim, observed = _run_observed(spec, tmp_path, scenario)
    assert observed.summary() == base.summary()
    assert observed.handover_log == base.handover_log
    assert validate_trace(load_trace(str(tmp_path / f"{scenario}.json"))) \
        == []


@settings(max_examples=8, deadline=None)
@given(nd=st.integers(min_value=2, max_value=12),
       ne=st.integers(min_value=1, max_value=4),
       rate=st.floats(min_value=0.5, max_value=12.0),
       seed=st.integers(min_value=0, max_value=2 ** 16),
       policy=st.sampled_from(["none", "bocd", "oracle"]))
def test_observer_neutrality_property(nd, ne, rate, seed, policy):
    spec = ScenarioSpec(
        name="obs-prop", seed=seed,
        planner=PlannerSpec(result_kb=4.0),
        topology=TopologySpec(kind="mobile", num_devices=nd, num_edges=ne,
                              speed=0.5, horizon_s=30.0),
        workload=WorkloadSpec(rate_hz=rate, horizon_s=5.0),
        router=RouterSpec(name="nearest"),
        mobility=MobilitySpec(policy=policy))
    base = Simulation(spec).run()
    sc = Simulation(spec).build()
    sc.engine.tracer = Tracer()
    sc.engine.timeline = Timeline(ne, num_devices=nd)
    sc.engine.profiler = SimProfiler()
    observed = sc.engine.run(sc.workload)
    assert observed.summary() == base.summary()
    assert observed.handover_log == base.handover_log
    if observed.summary()["requests"] > 0:
        assert validate_trace(sc.engine.tracer.to_chrome()) == []


# --------------------------------------------------- trace well-formedness


@pytest.fixture(scope="module")
def mobility_trace(tmp_path_factory):
    """One traced smoke-mobility run shared by the structural tests."""
    out = tmp_path_factory.mktemp("obs") / "trace.json"
    spec = get_scenario("smoke-mobility")
    spec = replace(spec, engine=replace(spec.engine, trace=str(out)))
    sim = Simulation(spec)
    summary = sim.run().summary()
    return load_trace(str(out)), summary, sim


def test_trace_valid_and_has_all_stages(mobility_trace):
    """The acceptance artifact: Perfetto-loadable, with spans for every
    lifecycle stage and per-edge counter tracks."""
    trace, summary, _ = mobility_trace
    assert validate_trace(trace) == []
    events = trace["traceEvents"]
    x_names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"queue", "uplink", "prefill", "decode", "round",
            "transfer"} <= x_names
    async_names = {e["name"] for e in events if e["ph"] in ("b", "e")}
    assert {"request", "queue", "handover"} <= async_names
    counter_names = {e["name"] for e in events if e["ph"] == "C"}
    assert {"backlog_s", "slots", "tokens_owed", "coop_inflight"} \
        <= counter_names
    # one request async pair per completed request
    begins = sum(1 for e in events
                 if e["ph"] == "b" and e["name"] == "request")
    assert begins == summary["requests"]


def test_trace_spans_nested_within_request_lifetime(mobility_trace):
    """Every per-request X span lies inside its request's async
    [begin, end] window, and durations are non-negative."""
    trace, _, _ = mobility_trace
    events = trace["traceEvents"]
    window = {}
    for e in events:
        if e["name"] == "request" and e["ph"] in ("b", "e"):
            lo, hi = window.get(e["id"], (None, None))
            window[e["id"]] = (e["ts"], hi) if e["ph"] == "b" \
                else (lo, e["ts"])
    eps = 1e-3          # trace-event us rounding slack
    checked = 0
    for e in events:
        if e["ph"] != "X":
            continue
        assert e["dur"] >= 0
        rid = (e.get("args") or {}).get("rid")
        if rid is None or rid not in window:
            continue
        lo, hi = window[rid]
        assert lo is not None and hi is not None
        assert e["ts"] >= lo - eps
        assert e["ts"] + e["dur"] <= hi + eps
        checked += 1
    assert checked > 0


def test_trace_monotone_per_track(mobility_trace):
    """Edge tracks emit in round order, so timestamps never regress within
    one (pid, tid) span track or one (pid, name) counter track.  (Device/
    net pseudo-process spans are emitted at *scheduling* time with future
    start stamps — deferred local starts — so only edge pids are strictly
    ordered; viewers sort by ts regardless.)"""
    trace, _, _ = mobility_trace
    last_x, last_c = {}, {}
    for e in trace["traceEvents"]:
        if e.get("pid", 0) >= Tracer.PID_DEVICES:
            continue
        if e["ph"] == "X":
            key = (e["pid"], e["tid"])
            assert e["ts"] >= last_x.get(key, -1.0)
            last_x[key] = e["ts"]
        elif e["ph"] == "C":
            key = (e["pid"], e["name"])
            assert e["ts"] >= last_c.get(key, -1.0)
            last_c[key] = e["ts"]
    assert last_x and last_c


def test_rerun_event_counts_identical(mobility_trace):
    """Satellite (b): the per-kind event counts are part of the
    deterministic contract — identical across reruns of one engine."""
    _, _, sim = mobility_trace
    sc = sim.scenario
    a = (sc.engine.events_processed, dict(sc.engine.event_counts))
    sc.engine.run(sc.workload)
    b = (sc.engine.events_processed, dict(sc.engine.event_counts))
    assert a == b
    assert a[0] == sum(v for k, v in a[1].items() if k != "sample") \
        + a[1].get("sample", 0) * sc.topo.num_devices


# ----------------------------------------------------------------- timeline


def test_timeline_roundtrip(tmp_path):
    spec = _small_mobility_spec()
    path = tmp_path / "tl.jsonl"
    spec = replace(spec, engine=replace(spec.engine, timeline=str(path)))
    sim = Simulation(spec)
    sim.run()
    tl = sim.scenario.engine.timeline
    assert tl.num_retained > 0
    loaded = load_timeline(str(path))
    assert loaded["header"]["samples"] == tl.num_retained
    assert loaded["header"]["edge_gauges"] == list(EDGE_GAUGES)
    assert loaded["t"].shape == (tl.num_retained,)
    for g in EDGE_GAUGES:
        assert loaded["edge"][g].shape == (tl.num_retained, 3)
    # mobility runs carry the per-device signals the sweep computed
    assert loaded["device"]["bw_bps"].shape == (tl.num_retained, 10)
    assert np.all(np.diff(loaded["t"]) > 0)
    # completions are cumulative, hence monotone per edge
    assert np.all(np.diff(loaded["edge"]["completed"], axis=0) >= 0)


def test_timeline_static_fleet_uses_obs_events(tmp_path):
    """Fleets with no sampling sweep get dedicated 'obs' ticks — and those
    must not change the summary either."""
    spec = ScenarioSpec(
        name="obs-static", seed=3,
        topology=TopologySpec(num_devices=8, num_edges=2),
        workload=WorkloadSpec(rate_hz=10.0, horizon_s=5.0))
    base = Simulation(spec).run().summary()
    path = tmp_path / "tl.jsonl"
    traced = replace(spec, engine=replace(spec.engine, timeline=str(path),
                                          timeline_dt=0.25))
    sim = Simulation(traced)
    s = sim.run().summary()
    assert s == base
    engine = sim.scenario.engine
    assert engine.event_counts.get("obs", 0) > 0
    assert load_timeline(str(path))["header"]["dt"] == 0.25


def test_timeline_ring_overwrites_oldest():
    tl = Timeline(1, dt=1.0, capacity=4)

    class _Edge:
        tokens_owed = 0
        active = ()
        queue = ()
        q_dead = 0
        coop_inflight = 0
        busy_s = 0.0
        completed = 0
        capacity = 8

        def backlog_s(self):
            return 0.0

    class _Topo:
        edges = [_Edge()]

    for t in range(6):
        tl.snapshot(float(t), _Topo())
    assert tl.n == 6 and tl.num_retained == 4
    assert [r["t"] for r in tl.rows()] == [2.0, 3.0, 4.0, 5.0]


# ----------------------------------------------------------------- profiler


def test_profiler_report(tmp_path):
    spec = _small_mobility_spec()
    sim = Simulation(spec)
    sc = sim.build()
    prof = SimProfiler()
    prof.build_s = sim.build_s
    sc.engine.profiler = prof
    base = Simulation(spec).run().summary()
    s = sc.engine.run(sc.workload).summary()
    assert s == base                    # profiling is neutral too
    rep = prof.report(sc.engine)
    assert rep["wall_s"] > 0
    assert rep["peak_heap"] > 0
    assert rep["build_s"] is not None
    assert set(rep["events"]) == set(sc.engine.event_counts)
    for kind, block in rep["events"].items():
        assert block["count"] == sc.engine.event_counts[kind]
    assert 0.0 <= rep["tombstone_ratio"] <= 1.0
    caches = rep["stepper_caches"]
    assert set(caches) == {"plan", "step", "hop", "jit", "decode", "arena"}
    assert caches["plan"]["hits"] + caches["plan"]["misses"] > 0
    # nearest-routing mobility replans via the JointPlanner
    assert set(rep["replanner_caches"]) == {"score", "ordered_sets"}


def test_profiler_reset_keeps_build_s():
    prof = SimProfiler()
    prof.build_s = 1.25
    prof.add("round", 0.5, heap_len=10)
    prof.reset()
    assert prof.run_wall_s == 0.0 and prof.peak_heap == 0
    assert prof.report()["build_s"] == 1.25


# ------------------------------------------- host spans on the device clock
#: each span of the real-decode path and the enclosing spans it may have
#: (``None``: no enclosing program span); a device-tier request prefills
#: inside its arrival's dispatch, an edge request inside its admission
SPAN_PARENTS = {
    "fleet.event.arrival": {None}, "fleet.event.round": {None},
    "fleet.round": {"fleet.event.round", "fleet.event.arrival"},
    "fleet.admit": {"fleet.round"},
    "fleet.prefill": {"fleet.admit", "fleet.event.arrival"},
    "arena.scatter": {"fleet.admit"},
    "fleet.price": {"fleet.round"},
    "arena.inputs": {"fleet.round"}, "arena.dispatch": {"fleet.round"},
    "fleet.epilogue": {"fleet.round"}, "fleet.emit": {"fleet.epilogue"},
    "fleet.retire": {"fleet.event.round"},
}


def _real_arena_spec():
    from repro.fleet.workload import TenantClass
    from repro.sim import EngineSpec
    return ScenarioSpec(
        name="obs-real-arena", seed=5,
        topology=TopologySpec(num_devices=6, num_edges=1, trace="lte",
                              edge_capacity=4),
        workload=WorkloadSpec(rate_hz=8.0, horizon_s=1.5, prompt_len=6,
                              tenants=(TenantClass("chat", slo_s=2.0,
                                                   max_new_tokens=4,
                                                   weight=1.0),)),
        engine=EngineSpec(real_decode=True, arena_decode=True))


def _program_spans(xplane):
    """``[(name, start_ns, end_ns, parent)]`` of the program's spans on
    the host plane's lines; the parent is the innermost enclosing program
    span on the same line."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(("fleet.", "arena."))),
                         key=lambda ev: (ev[1], -ev[2]))
            stack = []
            for name, s, e in evs:
                while stack and stack[-1][2] <= s:
                    stack.pop()
                out.append((name, s, e, stack[-1][0] if stack else None))
                stack.append((name, s, e))
    return out


def test_host_spans_land_on_the_device_trace_with_their_parents(tmp_path):
    import jax
    sc = Simulation(_real_arena_spec()).build()
    sc.engine.run(sc.workload)                   # compile outside the trace
    sc.engine.profiler = prof = SimProfiler()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        sc.engine.run(sc.workload)
    xplane, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = _program_spans(xplane)
    seen = {name for name, *_ in spans}
    assert set(SPAN_PARENTS) <= seen
    for name, s, e, parent in spans:
        if name.startswith("fleet.event."):
            assert parent is None, name
        else:
            assert parent in SPAN_PARENTS[name], (name, parent)
    # the trace and the profiler's own table count the same spans
    counts = {}
    for name, *_ in spans:
        counts[name] = counts.get(name, 0) + 1
    rep = prof.report(sc.engine)
    for name, block in rep["spans"].items():
        assert counts[name] == block["count"], name
    for kind, block in rep["events"].items():
        assert counts["fleet.event." + kind] == block["count"], kind


def test_profiler_off_enters_no_span(monkeypatch):
    """With ``engine.profiler`` None no annotation is ever made; the
    same run with a profiler attached does make them."""
    import repro.obs.profile as profile_mod

    class Refused:
        def __init__(self, *a, **k):
            raise AssertionError("span code ran with the profiler off")

    sc = Simulation(_real_arena_spec()).build()
    base = sc.engine.run(sc.workload).summary()
    monkeypatch.setattr(profile_mod, "TraceAnnotation", Refused)
    assert sc.engine.profiler is None
    assert sc.engine.run(sc.workload).summary() == base
    sc.engine.profiler = SimProfiler()
    with pytest.raises(AssertionError, match="profiler off"):
        sc.engine.run(sc.workload)


def test_profiler_span_and_counters_unit():
    prof = SimProfiler()
    with prof.span("fleet.round", edge=0, active=2):
        with prof.span("fleet.emit"):
            pass
    prof.arrived(7)
    prof.admitted(7)
    prof.admitted(8)                     # a re-admission: no arrival
    prof.host_reads += 3
    rep = prof.report()
    assert rep["spans"]["fleet.round"]["count"] == 1
    assert rep["spans"]["fleet.round"]["wall_s"] >= \
        rep["spans"]["fleet.emit"]["wall_s"]
    c = prof.counters()
    assert c["host_reads"] == 3 and len(c["queue_waits"]) == 1
    assert c["queue_waits"][0][1] >= 0.0
    with pytest.raises(KeyError):          # an exception unwinds cleanly
        with prof.span("fleet.admit"):
            raise KeyError
    assert prof.span_count["fleet.admit"] == 1
    prof.reset()
    assert prof.counters() == {"host_reads": 0, "queue_waits": []}
    assert "spans" not in prof.report()


# ---------------------------------------------------------------------- CLI


def test_obs_report_and_validate_cli(tmp_path, capsys):
    from repro.obs.report import main as obs_main
    from repro.sim.cli import main as sim_main
    trace = tmp_path / "t.json"
    tl = tmp_path / "t.jsonl"
    rc = sim_main(["--scenario", "smoke-mobility",
                   "--trace", str(trace), "--timeline", str(tl), "--json"])
    assert rc == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["events"]["processed"] > 0
    assert payload["events"]["by_kind"]["handover"] > 0
    assert payload["metrics"]["requests"] > 0

    assert obs_main(["validate", str(trace)]) == 0
    assert "valid Chrome trace" in capsys.readouterr().out

    assert obs_main(["report", str(trace)]) == 0
    out = capsys.readouterr().out
    for stage in ("queue", "uplink", "prefill", "decode", "transfer",
                  "handover", "request e2e", "edge utilization"):
        assert stage in out

    assert obs_main(["report", str(tl)]) == 0
    out = capsys.readouterr().out
    assert "timeline:" in out and "backlog_s" in out


def test_obs_validate_rejects_broken_trace(tmp_path, capsys):
    from repro.obs.report import main as obs_main
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"name": "x", "ph": "X", "pid": 0, "tid": 0, "ts": 1.0,
         "dur": -5.0},
        {"name": "q", "ph": "e", "cat": "req", "id": 1, "pid": 0,
         "tid": 0, "ts": 2.0},
    ]}))
    assert obs_main(["validate", str(bad)]) == 1
    err = capsys.readouterr().err
    assert "negative duration" in err and "async end before begin" in err


def test_trace_validate_helpers():
    assert validate_trace({}) == ["no traceEvents array"]
    t = Tracer()
    t.complete("a", 0.0, 1.0, 0, 0)
    t.async_begin("r", 1, 0.0, 0, 0)
    t.async_end("r", 1, 2.0, 0, 0)
    t.counter("c", 0.5, 0, {"v": 1.0})
    assert validate_trace(t.to_chrome()) == []
    t.async_begin("r", 2, 3.0, 0, 0)    # left open
    problems = validate_trace(t.to_chrome())
    assert any("unbalanced" in p for p in problems)
