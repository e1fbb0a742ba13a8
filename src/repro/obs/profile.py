"""Simulator self-profiling: where the engine's wall time actually goes.

A :class:`SimProfiler` attached to a :class:`~repro.fleet.engine
.FleetEngine` times every event-handler dispatch (wall seconds and count
per event kind) and tracks the event heap's peak size.  ``report()`` folds
in the engine-side structural stats — queue tombstone ratio, the shared
:class:`~repro.serving.engine.CoInferenceStepper` cache hit rates, the
mobility replanner's cache hit rates — plus the scenario build time when
the caller stamps ``build_s``.

On a real-decode engine it is also the program's host-span and counter
layer (docs/observability.md, "Host spans on the device clock"): every
span is timed on ``time.perf_counter`` *and* entered as a
``jax.profiler.TraceAnnotation`` of the same name, so under
``jax.profiler.trace`` it lands in the device trace, on the device
trace's clock, nested under its enclosing span.  Counters: ``host_reads``
(blocking device->host reads on the decode path) and ``queue_waits``
(per admission, the host seconds since the engine handled the request's
arrival).

This is the measurement side of the ROADMAP's 100k-device scaling push:
``benchmarks/perf_fleet.py --smoke`` attaches one per cell and emits the
report as the cell's ``profile`` block.  Unlike the tracer/timeline, a
profiler reads *host* clocks, so its numbers vary run to run — but it
never touches simulation state, so virtual-time results remain
bit-identical with profiling on or off.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["SimProfiler"]


class _Span:
    """One timed, annotated span; exits cleanly when an exception (a
    caller ending the run) unwinds through it."""
    __slots__ = ("_prof", "_name", "_ann", "_evq", "_t0")

    def __init__(self, prof, name, ann, evq=None):
        self._prof, self._name, self._ann, self._evq = prof, name, ann, evq

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._evq is None:
            self._prof._span_done(self._name, dt)
        else:
            self._prof.add(self._name, dt, len(self._evq))


class SimProfiler:
    def __init__(self):
        self.build_s: Optional[float] = None   # stamped by the builder;
        #                                        survives reset()
        self.reset()

    def reset(self) -> None:
        """Clear per-run accumulators (the engine calls this per run);
        ``build_s`` is construction-time metadata and is kept."""
        self.wall_by_kind: Dict[str, float] = {}
        self.count_by_kind: Dict[str, int] = {}
        self.peak_heap = 0
        self.run_wall_s = 0.0
        self.span_wall: Dict[str, float] = {}
        self.span_count: Dict[str, int] = {}
        self.host_reads = 0
        # (admission start, its wait since the arrival was handled), both
        # on time.perf_counter; arrivals wait here keyed by request id
        self.queue_waits: List[Tuple[float, float]] = []
        self._arrived: Dict[object, float] = {}

    def add(self, kind: str, wall_s: float, heap_len: int) -> None:
        """Account one dispatched event of ``kind`` (called by the engine
        loop with the post-dispatch heap length)."""
        self.wall_by_kind[kind] = self.wall_by_kind.get(kind, 0.0) + wall_s
        self.count_by_kind[kind] = self.count_by_kind.get(kind, 0) + 1
        if heap_len > self.peak_heap:
            self.peak_heap = heap_len
        self.run_wall_s += wall_s

    # ------------------------------------------------------- host spans
    def event(self, kind: str, evq) -> _Span:
        """The dispatch of one event: span ``fleet.event.<kind>``, timed
        into the per-kind table (:meth:`add`)."""
        return _Span(self, kind, TraceAnnotation("fleet.event." + kind), evq)

    def span(self, name: str, **args) -> _Span:
        """A context manager timing ``name`` into the span table and
        annotating it (with ``args``) for the device trace."""
        return _Span(self, name, TraceAnnotation(name, **args))

    def _span_done(self, name: str, wall_s: float) -> None:
        self.span_wall[name] = self.span_wall.get(name, 0.0) + wall_s
        self.span_count[name] = self.span_count.get(name, 0) + 1

    # --------------------------------------------------------- counters
    def arrived(self, rid) -> None:
        """The engine handled ``rid``'s arrival and queued it at an edge."""
        self._arrived[rid] = time.perf_counter()

    def admitted(self, rid) -> None:
        """``rid`` leaves the queue for the batch: one queue-wait sample.
        A re-admission after a migration has no arrival stamp and gives
        no sample."""
        t_arr = self._arrived.pop(rid, None)
        if t_arr is not None:
            t = time.perf_counter()
            self.queue_waits.append((t, t - t_arr))

    def counters(self) -> Dict:
        """The counters as plain data: ``host_reads`` and ``queue_waits``
        (``[admission start, wait]`` pairs on ``time.perf_counter``)."""
        return {"host_reads": self.host_reads,
                "queue_waits": [list(w) for w in self.queue_waits]}

    def report(self, engine=None) -> Dict:
        """The ``profile`` block: per-kind wall time/counts, heap peak, and
        — given the engine — tombstone ratio and cache hit rates; on a
        real-decode run also the host spans and counters."""
        total = self.run_wall_s
        out: Dict = {
            "wall_s": round(total, 6),
            "peak_heap": self.peak_heap,
            "events": {
                kind: {"count": self.count_by_kind[kind],
                       "wall_s": round(self.wall_by_kind[kind], 6),
                       "wall_pct": round(
                           100.0 * self.wall_by_kind[kind] / total, 2)
                       if total > 0 else 0.0}
                for kind in sorted(self.count_by_kind)},
        }
        if self.span_count:
            out["spans"] = {name: {"count": self.span_count[name],
                                   "wall_s": round(self.span_wall[name], 6)}
                            for name in sorted(self.span_count)}
            out["host_reads"] = self.host_reads
            out["queue_waits"] = len(self.queue_waits)
        if self.build_s is not None:
            out["build_s"] = round(self.build_s, 6)
        if engine is not None:
            enqueued = getattr(engine, "enqueued", 0)
            tombstoned = getattr(engine, "tombstoned", 0)
            out["tombstones"] = tombstoned
            out["tombstone_ratio"] = round(tombstoned / enqueued, 6) \
                if enqueued else 0.0
            stepper = getattr(engine, "stepper", None)
            if stepper is not None and hasattr(stepper, "cache_stats"):
                out["stepper_caches"] = stepper.cache_stats()
            replanner = getattr(engine, "replanner", None)
            if replanner is not None and hasattr(replanner, "cache_stats"):
                out["replanner_caches"] = replanner.cache_stats()
        return out
