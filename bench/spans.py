"""The program's host spans in a profiler trace: the chip's idle time put
down to the host work that left it idle.

A real-decode ``FleetEngine`` with a ``repro.obs.SimProfiler`` attached
annotates its host work with spans named ``fleet.*`` and ``arena.*``
(docs/observability.md), on the trace's own clock.  :func:`reduce` takes
them out of the planes, so that ``bench.trace.reduce`` computes busy time,
modules and the runtime-event names of the idle gaps exactly as on a
trace without them, and then gives each stretch of device idle time in
the window to the innermost program span that covers it on the host.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bench import trace as T

PREFIXES = ("fleet.", "arena.")
#: spans whose idle time is the epilogue's and the next step's inputs'
EPILOGUE = ("fleet.epilogue", "fleet.emit", "arena.inputs")
#: spans whose idle time is admission's
ADMIT = ("fleet.admit", "fleet.prefill", "arena.scatter")


def group(name: str) -> str:
    """``epilogue``, ``admit`` or ``loop`` (every other program span)."""
    return "epilogue" if name in EPILOGUE else \
        "admit" if name in ADMIT else "loop"


def split(planes) -> Tuple[list, List[Tuple[str, float, float]]]:
    """The planes without program spans, and the spans as ``(name, start,
    end)`` in nanoseconds."""
    rest, spans = [], []
    for pname, lines in planes:
        if pname.startswith("/host:"):
            kept = []
            for lname, evs in lines:
                kept.append((lname, [ev for ev in evs
                                     if not ev[0].startswith(PREFIXES)]))
                spans += [(n, s, s + d) for n, s, d in evs
                          if n.startswith(PREFIXES)]
            lines = kept
        rest.append((pname, lines))
    return rest, spans


def innermost(spans) -> List[Tuple[float, float, str]]:
    """``(start, end, name)`` stretches in time order: each lies inside
    ``name`` and inside no span nested in it."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []
    t = None

    def close(upto):
        nonlocal t
        while stack and stack[-1][1] <= upto:
            name, end = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end

    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        close(s)
        if stack and s > t:
            segs.append((t, s, stack[-1][0]))
        t = s
        stack.append((name, e))
    close(float("inf"))
    return segs


def idle_gaps(planes, window) -> List[Tuple[float, float]]:
    """The first chip's idle stretches in ``window``, as
    ``bench.trace.reduce`` finds them."""
    w0, w1 = window
    for pname, lines in sorted(p for p in planes
                               if re.match(r"^/device:(TPU|GPU):\d+$", p[0])):
        ops = T._merge(T._clip([(s, s + d) for _, s, d in
                                dict(lines).get("XLA Ops", ())], w0, w1))
        if ops:
            edges = [w0] + [x for ab in ops for x in ab] + [w1]
            return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    return []


def _window(planes) -> Tuple[float, float]:
    for pname, lines in planes:
        if pname.startswith("/host:"):
            for _, evs in lines:
                for name, s, d in evs:
                    if name == T.WINDOW:
                        return s, s + d
    raise ValueError(f"the trace has no {T.WINDOW!r} host event")


def _covering(spans, a, b) -> Optional[str]:
    best = None
    for name, s, e in spans:
        if s <= a and e >= b and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else None


def reduce(planes, n_top: int = 10) -> Dict:
    """``bench.trace.reduce`` of the planes without program spans, plus:

    * ``spans``: per span name, ``count`` (spans overlapping the window),
      ``seconds`` (host time inside it) and ``idle_s`` (device idle time
      whose innermost covering span it is);
    * ``idle_uncovered_s``: device idle time that no span covers;
    * ``breakdown["idle_by_span"]``: ``[name, idle_s]``, most first;
    * each named idle gap that a program span covers ends in
      `` in <innermost covering span>``."""
    rest, spans = split(planes)
    red = T.reduce(rest, n_top)
    w0, w1 = _window(planes)
    spans = [(n, s, e) for n, s, e in spans if e > w0 and s < w1]
    table: Dict[str, Dict] = {}
    for name, s, e in spans:
        row = table.setdefault(name, {"count": 0, "seconds": 0.0,
                                      "idle_s": 0.0})
        row["count"] += 1
        row["seconds"] += (min(e, w1) - max(s, w0)) * 1e-9
    gaps = idle_gaps(rest, (w0, w1))
    segs = innermost(spans)
    idle = sum(b - a for a, b in gaps)
    covered, i = 0.0, 0
    for a, b in gaps:
        while i < len(segs) and segs[i][1] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][0] < b:
            o = min(b, segs[j][1]) - max(a, segs[j][0])
            if o > 0:
                table[segs[j][2]]["idle_s"] += o * 1e-9
                covered += o
            j += 1
    red["spans"] = table
    red["idle_uncovered_s"] = (idle - covered) * 1e-9
    red["breakdown"]["idle_by_span"] = sorted(
        ([k, v["idle_s"]] for k, v in table.items() if v["idle_s"] > 0),
        key=lambda kv: -kv[1])
    top = sorted(gaps, key=lambda g: g[0] - g[1])[:n_top]
    for row, (a, b) in zip(red["breakdown"]["idle_gaps"], top):
        inner = _covering(spans, a, b)
        if inner is not None:
            row[0] += f" in {inner}"
    return red


def idle_share(trace: Optional[Dict], which: str) -> Optional[float]:
    """Percent of the traced window that the chip sat idle inside spans
    of group ``which``; ``None`` for a trace without program spans or
    without device operations."""
    if not trace or not trace.get("spans") or not trace["busy_s"]:
        return None
    idle = sum(v["idle_s"] for k, v in trace["spans"].items()
               if group(k) == which)
    return 100.0 * idle / trace["window_s"]
