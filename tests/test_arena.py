"""Slot-resident decode arena suite (docs/performance.md).

* bit-identity: the masked full-arena call (``decode_fn_arena``) produces
  token streams identical to the serial per-request path under fuzzed
  mixed exits, mixed prompt lengths, and slot churn — admits, evicts, and
  extract -> re-admit handovers mid-stream (hypothesis + a fixed-seed
  variant that always runs);
* fleet-level pins: a static real-decode scenario and a mobile BOCD
  scenario with ``handovers > 0`` are token- and summary-identical with
  ``arena_decode`` on vs off, while compiling at most one arena variant
  per model exit and padding zero rows;
* arena mechanics: ``extract`` returns a cache bitwise equal to the
  admitted one (sliced back from the padded row), slot/length growth
  doubles and re-buckets without disturbing resident rows, and the free
  list hands out lowest slots first;
* spec plumbing: ``EngineSpec`` validates ``arena_bucket``; sweep rows
  carry the decode-efficiency columns only for real-decode cells; the
  tracer's ``decode_stats`` metadata event validates and renders as the
  report's decode panel.
"""
import dataclasses
import json

import numpy as np
import pytest

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.serving.arena import DecodeArena, pow2
from repro.serving.engine import CoInferenceStepper
from repro.sim import (EngineSpec, PlannerSpec, RouterSpec, ScenarioSpec,
                       Simulation, TopologySpec, WorkloadSpec, get_scenario)
from repro.sim.build import build_stack


@pytest.fixture(scope="module")
def stack():
    return build_stack(PlannerSpec(), with_model=True)


def _prefill_row(stack, *, prompt_len, extra, seed):
    """One B=1 (cache, tok) row after a real prefill (the fleet's request
    state at decode start)."""
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(
        rng.integers(0, stack.cfg.vocab_size, (1, prompt_len)), jnp.int32)
    cache = stack.model.init_cache(1, prompt_len + extra + 1,
                                   dtype=jnp.float32, enc_len=prompt_len)
    h, cache = stack.model.prefill(stack.params, toks, cache)
    logits = stack.model.logits(stack.params, h)
    tok = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]
    return cache, tok


def _next_tok(stack, h):
    import jax.numpy as jnp
    logits = stack.model.logits(stack.params, h)
    return jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)[:, None]


# ------------------------------------------------------------- bit-identity
def _churn_tokens(stack, plan, *, arena_slots=2, arena_len=8,
                  handover_steps=()):
    """Decode ``plan`` rows (dicts with prompt_len/extra/exit/start/steps)
    twice — serial per-request vs slot-resident arena with churn — and
    return both token-stream dicts.

    Requests join at their ``start`` step (admit), leave after ``steps``
    decoded tokens (evict); at every step in ``handover_steps`` each
    active request is extracted and re-admitted (the handover motion),
    scrambling slot assignments mid-stream."""
    import jax.numpy as jnp
    rows = {i: _prefill_row(stack, prompt_len=p["prompt_len"],
                            extra=p["extra"], seed=1000 + i)
            for i, p in enumerate(plan)}
    horizon = max(p["start"] + p["steps"] for p in plan)

    # --- serial baseline
    stepper_s = CoInferenceStepper(stack.model, stack.graph, stack.planner)
    serial = {i: [] for i in rows}
    state = {i: rows[i] for i in rows}
    for step in range(horizon):
        for i, p in enumerate(plan):
            if not p["start"] <= step < p["start"] + p["steps"]:
                continue
            cache, tok = state[i]
            pos = p["prompt_len"] + (step - p["start"])
            fn = stepper_s.decode_fn(p["exit"])
            h, cache = fn(stack.params, cache, tok,
                          jnp.asarray(pos, jnp.int32))
            tok = _next_tok(stack, h)
            serial[i].append(int(tok[0, 0]))
            state[i] = (cache, tok)

    # --- arena path with churn
    stepper_a = CoInferenceStepper(stack.model, stack.graph, stack.planner)
    arena = DecodeArena(stack.model, slots=arena_slots, length=arena_len,
                        dtype=jnp.float32, stepper=stepper_a)
    got = {i: [] for i in rows}
    toks = {i: rows[i][1] for i in rows}
    for step in range(horizon):
        for i, p in enumerate(plan):          # admits (possibly mid-stream)
            if step == p["start"]:
                arena.admit(i, rows[i][0])
        if step in handover_steps:            # extract -> re-admit everyone
            resident = [i for i in rows if arena.has(i)]
            snaps = {i: arena.extract(i) for i in resident}
            for i in reversed(resident):
                arena.admit(i, snaps[i])
        items = []
        for i, p in enumerate(plan):
            if p["start"] <= step < p["start"] + p["steps"]:
                pos = p["prompt_len"] + (step - p["start"])
                items.append((p["exit"], arena.slot(i), toks[i], pos))
        if items:
            outs = stepper_a.decode_step_arena(stack.params, arena, items)
            nts = {}
            for group_rows, h_all in outs:   # grouped epilogue, as the fleet
                la = stack.model.logits(stack.params, h_all[:, 0])
                nt = jnp.argmax(la[:, -1, :], -1).astype(jnp.int32)
                for _, slot, _, _ in group_rows:
                    nts[slot] = nt[slot][None, None]
            for i, p in enumerate(plan):
                if p["start"] <= step < p["start"] + p["steps"]:
                    toks[i] = nts[arena.slot(i)]
                    got[i].append(int(toks[i][0, 0]))
        for i, p in enumerate(plan):          # evicts at end-of-stream
            if step == p["start"] + p["steps"] - 1:
                arena.evict(i)
    return serial, got


def _plan_from_seed(stack, seed, n):
    rng = np.random.default_rng(seed)
    n_exits = stack.graph.num_exits
    return [{"prompt_len": int(rng.integers(3, 9)),
             "extra": int(rng.integers(3, 10)),
             "exit": 1 + int(rng.integers(n_exits)),
             "start": int(rng.integers(0, 3)),
             "steps": int(rng.integers(2, 5))} for _ in range(n)]


def test_arena_decode_bit_identical_fixed_seed(stack):
    """Mixed exits, mixed prompt lengths, mid-stream admits/evicts and a
    forced extract->re-admit handover: arena tokens == serial tokens."""
    plan = _plan_from_seed(stack, 42, 4)
    serial, got = _churn_tokens(stack, plan, arena_slots=2, arena_len=4,
                                handover_steps=(2,))
    assert serial == got


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 50), n=st.integers(2, 4),
       handover=st.booleans())
def test_property_arena_decode_bit_identical(stack, seed, n, handover):
    plan = _plan_from_seed(stack, seed, n)
    serial, got = _churn_tokens(
        stack, plan, arena_slots=1, arena_len=4,
        handover_steps=(1,) if handover else ())
    assert serial == got


def test_arena_counters_and_variant_budget(stack):
    """The churn run compiles at most one arena variant per model exit per
    arena geometry and counts masked rows for the occupancy metric."""
    import jax.numpy as jnp
    stepper = CoInferenceStepper(stack.model, stack.graph, stack.planner)
    arena = DecodeArena(stack.model, slots=4, length=16, dtype=jnp.float32,
                        stepper=stepper)
    rows = [_prefill_row(stack, prompt_len=4, extra=4, seed=i)
            for i in range(2)]
    for i, (cache, _) in enumerate(rows):
        arena.admit(i, cache)
    items = [(1, arena.slot(i), rows[i][1], 4) for i in range(2)]
    for _ in range(3):
        stepper.decode_step_arena(stack.params, arena, items)
    st_ = stepper.cache_stats()
    assert st_["arena"]["calls"] == 3
    assert st_["arena"]["tokens"] == 6
    assert st_["arena"]["masked_rows"] == 3 * (arena.slots - 2)
    assert st_["arena"]["occupancy"] == round(6 / (6 + 6), 4)
    assert st_["jit"]["variants"]["arena"] == 1
    assert st_["decode"]["padded_rows"] == 0   # arena path never pads


# ------------------------------------------------------------ arena object
def test_extract_roundtrip_bitwise(stack):
    """admit -> extract returns the exact cache: every leaf bitwise equal,
    shapes restored from the padded arena row."""
    import jax
    import jax.numpy as jnp
    cache, _ = _prefill_row(stack, prompt_len=5, extra=3, seed=0)
    arena = DecodeArena(stack.model, slots=2, length=32, dtype=jnp.float32)
    arena.admit("r", cache)
    out = arena.extract("r")
    flat_in = jax.tree_util.tree_leaves(cache)
    flat_out = jax.tree_util.tree_leaves(out)
    assert len(flat_in) == len(flat_out)
    for a, b in zip(flat_in, flat_out):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not arena.has("r") and arena.active == 0


def test_arena_growth_slots_and_length(stack):
    """Admitting past capacity doubles slots; a longer-than-arena cache
    re-buckets the length — resident rows still extract bitwise."""
    import jax
    import jax.numpy as jnp
    small, _ = _prefill_row(stack, prompt_len=4, extra=2, seed=1)
    big, _ = _prefill_row(stack, prompt_len=4, extra=40, seed=2)
    stepper = CoInferenceStepper(stack.model, stack.graph, stack.planner)
    arena = DecodeArena(stack.model, slots=1, length=4, dtype=jnp.float32,
                        stepper=stepper)
    assert arena.slots == 1 and arena.length == 4
    arena.admit("a", small)                          # true len 7: len 4 -> 8
    assert arena.length == 8
    arena.admit("b", small)                          # slot growth: 1 -> 2
    assert arena.slots == 2
    arena.admit("c", big)                            # len 8 -> 64 and 2 -> 4
    assert arena.slots == 4 and arena.length == 64
    assert stepper.arena_grows == 4
    for rid, src in (("a", small), ("c", big)):
        got = jax.tree_util.tree_leaves(arena.extract(rid))
        for x, y in zip(jax.tree_util.tree_leaves(src), got):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_arena_free_list_prefers_lowest_slot(stack):
    import jax.numpy as jnp
    cache, _ = _prefill_row(stack, prompt_len=4, extra=2, seed=3)
    arena = DecodeArena(stack.model, slots=4, length=16, dtype=jnp.float32)
    assert [arena.admit(r, cache) for r in "abc"] == [0, 1, 2]
    arena.evict("a")
    assert arena.admit("d", cache) == 0   # lowest free slot, deterministic
    assert arena.slot("b") == 1


def test_arena_rejects_bad_bucket(stack):
    import jax.numpy as jnp
    with pytest.raises(ValueError, match="bucket"):
        DecodeArena(stack.model, slots=1, length=4, dtype=jnp.float32,
                    bucket="linear")


def test_pow2():
    assert [pow2(n) for n in (1, 2, 3, 4, 5, 8, 9, 17)] == \
        [1, 2, 4, 4, 8, 8, 16, 32]


# ------------------------------------------------------------- fleet pins
def _static_spec(arena: bool, *, batch: bool = True) -> ScenarioSpec:
    from repro.fleet.workload import TenantClass
    tenants = (TenantClass("interactive", slo_s=1.0, max_new_tokens=6,
                           weight=0.5),
               TenantClass("standard", slo_s=2.0, max_new_tokens=10,
                           weight=0.5))
    return ScenarioSpec(
        name="arena-static", seed=3,
        topology=TopologySpec(num_devices=8, num_edges=2, trace="lte",
                              edge_capacity=8, max_edge_slowdown=2.0),
        workload=WorkloadSpec(rate_hz=10.0, horizon_s=4.0, device_skew=0.5,
                              prompt_len=6, tenants=tenants),
        router=RouterSpec(name="bandwidth-aware"),
        engine=EngineSpec(real_decode=True, batch_decode=batch,
                          arena_decode=arena))


def _run_fleet(spec):
    sim = Simulation(spec)
    m = sim.run()
    toks = {r.rid: list(r.tokens) for r in sim.scenario.workload}
    return m.summary(), toks, sim.scenario.engine.stepper.cache_stats()


def test_fleet_arena_equals_serial_static():
    """Static mixed-tenant real-decode fleet: token streams and summaries
    identical arena vs the serial engine (batch_decode=False), at most one
    compiled arena variant per model exit, zero padded rows."""
    s_off, t_off, _ = _run_fleet(_static_spec(False, batch=False))
    s_on, t_on, st_ = _run_fleet(_static_spec(True))
    assert t_on == t_off
    assert json.dumps(s_on, sort_keys=True) == \
        json.dumps(s_off, sort_keys=True)
    ar = st_["arena"]
    assert ar["calls"] > 0 and ar["tokens"] > 0
    assert ar["admits"] == ar["evicts"] > 0
    assert st_["decode"]["padded_rows"] == 0
    assert st_["decode"]["batched_calls"] == 0   # arena replaces the vmap path
    sc = build_stack(PlannerSpec())
    n_model = len(sc.graph.branches)             # model exits incl. full path
    assert 0 < st_["jit"]["variants"]["arena"] <= n_model


def test_fleet_arena_profiler_neutral_and_counts_host_reads():
    """A profiler on the real-decode arena path changes no token and no
    summary, and counts every blocking device-to-host read:

    * the epilogue reads each exit group's token vector once: one read
      per compiled arena call (``arena calls``);
    * the step's inputs read a token only while it is still on the
      device, which is the prefill's token at a request's first arena
      round: one read per admission (this scenario hands nothing over,
      so every admission carries a fresh prefill's token);
    * the serial path reads each token it decodes once.

    So ``host_reads == arena calls + admissions + serial tokens``; and
    the profiler records one non-negative queue wait per admission."""
    from repro.obs import SimProfiler
    sc = Simulation(_static_spec(True)).build()
    base = sc.engine.run(sc.workload).summary()
    toks = {r.rid: list(r.tokens) for r in sc.workload}
    st0 = sc.engine.stepper.cache_stats()
    sc.engine.profiler = prof = SimProfiler()
    assert sc.engine.run(sc.workload).summary() == base
    assert {r.rid: list(r.tokens) for r in sc.workload} == toks
    st1 = sc.engine.stepper.cache_stats()
    arena_tokens = st1["arena"]["tokens"] - st0["arena"]["tokens"]
    arena_calls = st1["arena"]["calls"] - st0["arena"]["calls"]
    admits = st1["arena"]["admits"] - st0["arena"]["admits"]
    serial = st1["decode"]["serial_tokens"] - st0["decode"]["serial_tokens"]
    assert arena_tokens > arena_calls > 0
    assert prof.host_reads == arena_calls + admits + serial
    assert sum(map(len, toks.values())) == arena_tokens + serial
    waits = prof.counters()["queue_waits"]
    assert len(waits) == admits
    assert all(w >= 0.0 for _, w in waits)
    assert [t for t, _ in waits] == sorted(t for t, _ in waits)


def _demote_spec(arena: bool) -> ScenarioSpec:
    """One edge, a tight and a loose tenant, deadline demotion on: the
    tight tenant's requests demote to earlier exits while the loose ones
    keep theirs, so arena rounds sweep one to three exit groups."""
    from repro.fleet.workload import TenantClass
    tenants = (TenantClass("tight", slo_s=0.1, max_new_tokens=8,
                           weight=0.5),
               TenantClass("loose", slo_s=4.0, max_new_tokens=8,
                           weight=0.5))
    return ScenarioSpec(
        name="arena-demote", seed=3,
        topology=TopologySpec(num_devices=8, num_edges=1, trace="lte",
                              edge_capacity=8, max_edge_slowdown=2.0),
        workload=WorkloadSpec(rate_hz=20.0, horizon_s=2.0, device_skew=0.5,
                              prompt_len=6, tenants=tenants),
        router=RouterSpec(name="bandwidth-aware"),
        engine=EngineSpec(real_decode=True, batch_decode=False,
                          arena_decode=arena, demote_on_deadline=True))


def test_fleet_arena_exit_groups_read_once_per_round():
    """Demoted requests split a round into several exit groups: tokens
    stay bit-identical to the serial engine, every slot leaves a round
    holding a ``(1, 1)`` int32 host ``next_tok``, and a round adds to
    ``host_reads`` exactly its exit groups (one vector read each) plus
    the prefill tokens it met still on the device."""
    from repro.obs import SimProfiler
    s_off, t_off, _ = _run_fleet(_demote_spec(False))
    sc = Simulation(_demote_spec(True)).build()
    eng = sc.engine
    eng.profiler = prof = SimProfiler()
    rounds = []
    orig = eng._decode_real_arena

    def traced(edge, reqs):
        on_device = sum(not isinstance(r.next_tok, np.ndarray)
                        for r in reqs)
        reads0, calls0 = prof.host_reads, eng.stepper.arena_calls
        orig(edge, reqs)
        groups = eng.stepper.arena_calls - calls0
        rounds.append((groups, on_device, prof.host_reads - reads0))
        for r in reqs:
            assert isinstance(r.next_tok, np.ndarray)
            assert r.next_tok.shape == (1, 1)
            assert r.next_tok.dtype == np.int32

    eng._decode_real_arena = traced
    s_on = eng.run(sc.workload).summary()
    t_on = {r.rid: list(r.tokens) for r in sc.workload}
    assert t_on == t_off
    assert json.dumps(s_on, sort_keys=True) == \
        json.dumps(s_off, sort_keys=True)
    assert all(reads == groups + on_device
               for groups, on_device, reads in rounds)
    assert max(groups for groups, _, _ in rounds) >= 2
    assert any(groups >= 2 and on_device == 0
               for groups, on_device, _ in rounds)


def _mobile_spec(arena: bool) -> ScenarioSpec:
    from repro.fleet.workload import TenantClass
    base = get_scenario("smoke-mobility")
    return dataclasses.replace(
        base, name="arena-mobility",
        topology=dataclasses.replace(base.topology, num_devices=12,
                                     num_edges=4, speed=1.5),
        workload=dataclasses.replace(
            base.workload, rate_per_device_hz=0.3, horizon_s=15.0,
            prompt_len=6, sample_prompts=True,
            tenants=(TenantClass("interactive", 1.0, 8, 0.5),
                     TenantClass("standard", 3.0, 16, 0.5))),
        mobility=dataclasses.replace(base.mobility, min_gap_s=0.5),
        engine=dataclasses.replace(base.engine, real_decode=True,
                                   arena_decode=arena))


@pytest.fixture(scope="module")
def mobile_runs():
    """The mobile fleet served serially and through the arena, the arena
    run logging each handover's ship (request id, source edge,
    destination edge, tokens done, ``next_tok``) and each admission
    (request id, edge, ``next_tok``)."""
    from repro.fleet.engine import FleetEngine
    ships, admits = [], []
    ship, admit = FleetEngine._ship, FleetEngine._admit_real

    def logged_ship(self, req, src_eid, dec, *args):
        ships.append((req.rid, src_eid, dec.primary, req.tokens_done,
                      req.next_tok))
        return ship(self, req, src_eid, dec, *args)

    def logged_admit(self, edge, req):
        admits.append((req.rid, edge.eid, req.next_tok))
        return admit(self, edge, req)

    off = _run_fleet(_mobile_spec(False))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(FleetEngine, "_ship", logged_ship)
        mp.setattr(FleetEngine, "_admit_real", logged_admit)
        on = _run_fleet(_mobile_spec(True))
    return off, on, ships, admits


@pytest.mark.slow
def test_fleet_arena_equals_serial_under_handover(mobile_runs):
    """Mobile BOCD fleet that actually hands requests over mid-stream
    (pinned handovers > 0): the extract -> ship -> re-admit motion keeps
    token streams and summaries bit-identical to the serial engine."""
    (s_off, t_off, _), (s_on, t_on, st_), _, _ = mobile_runs
    assert s_off.get("handovers", 0) > 0          # the pin with teeth
    assert t_on == t_off
    assert json.dumps(s_on, sort_keys=True) == \
        json.dumps(s_off, sort_keys=True)
    assert st_["arena"]["calls"] > 0
    assert st_["decode"]["padded_rows"] == 0


def test_handover_resumes_from_host_token_on_another_edge(mobile_runs):
    """A request extracted from an edge's arena after decode rounds
    ships with the ``(1, 1)`` int32 host token its last round left,
    is re-admitted into another edge's arena holding that same token,
    and finishes with the serial engine's tokens."""
    (_, t_off, _), (_, t_on, _), ships, admits = mobile_runs
    moved = [s for s in ships if s[3] > 0]
    assert moved
    for rid, src, dst, done, tok in moved:
        assert dst != src
        assert isinstance(tok, np.ndarray)
        assert tok.shape == (1, 1) and tok.dtype == np.int32
        assert int(tok[0, 0]) == t_on[rid][done - 1]
        assert [eid for r, eid, t in admits
                if r == rid and t is tok] == [dst]
        assert t_on[rid] == t_off[rid] and len(t_on[rid]) > done


def test_arena_off_matches_pre_pr_goldens():
    """arena_decode=False is the default: the calib suite's golden pins
    cover byte-identity, here we just pin the default itself."""
    assert EngineSpec().arena_decode is False
    assert EngineSpec().arena_bucket == "pow2"


# ------------------------------------------------------------ spec plumbing
def test_engine_spec_validates_arena_bucket():
    with pytest.raises(ValueError, match="arena_bucket"):
        EngineSpec(arena_bucket="nope")


def test_sweep_row_decode_columns():
    from repro.sim.sweep import run_cell
    row = run_cell(_static_spec(True))
    dec = row["decode"]
    assert dec["padded_rows"] == 0 and dec["pad_waste"] == 0.0
    assert dec["arena_calls"] > 0 and dec["arena_tokens"] > 0
    assert 0.0 < dec["arena_occupancy"] <= 1.0
    assert dec["jit_variants"]["arena"] >= 1
    # model-free cells carry no decode block at all
    plain = dataclasses.replace(
        _static_spec(False), engine=EngineSpec(real_decode=False))
    assert "decode" not in run_cell(plain)


# ------------------------------------------------------------ observability
def test_tracer_decode_stats_event_and_panel(tmp_path):
    from repro.obs import Tracer, validate_trace
    from repro.obs.report import render_trace
    spec = dataclasses.replace(
        _static_spec(True),
        engine=dataclasses.replace(_static_spec(True).engine,
                                   trace=str(tmp_path / "t.json")))
    sim = Simulation(spec)
    sim.run()
    trace = sim.scenario.engine.tracer.to_chrome()
    assert validate_trace(trace) == []
    evs = [e for e in trace["traceEvents"]
           if e.get("ph") == "M" and e.get("name") == "decode_stats"]
    assert len(evs) == 1
    args = evs[0]["args"]
    assert args["arena"]["calls"] > 0
    assert args["decode"]["padded_rows"] == 0
    txt = render_trace(trace)
    assert "decode efficiency" in txt
    assert "arena" in txt and "occupancy" in txt
