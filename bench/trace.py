"""Reduce a profiler trace of the window to device numbers.

The harness wraps the window in a host annotation named ``bench_window``,
so the window is known on the trace's own clock.  On each device plane,
``XLA Ops`` events give the intervals in which an operation ran, and
``XLA Modules`` events the compiled programs (named ``jit_<function>``,
with a suffix that is dropped here).  Busy time is the union of the op
intervals inside the window, averaged over the chips that ran anything;
idle gaps are the stretches between them on the first chip, each named
after the host event that overlaps it most.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, List, Tuple

WINDOW = "bench_window"
_SUFFIX = re.compile(r"\(.*\)$")


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, w0, w1):
    return [(max(a, w0), min(b, w1)) for a, b in iv if b > w0 and a < w1]


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name).strip()


def reduce(planes, n_top: int = 10) -> Dict:
    """``planes``: ``[(plane name, [(line name, [(event name, start_ns,
    duration_ns)])])]``, as :func:`load` reads them."""
    host_events, window = [], None
    devices = []
    for pname, lines in planes:
        if pname.startswith("/host:"):
            for _, evs in lines:
                for name, s, d in evs:
                    if name == WINDOW:
                        window = (s, s + d)
                    else:
                        host_events.append((name, s, s + d))
        elif re.match(r"^/device:(TPU|GPU):\d+$", pname):
            devices.append((pname, dict(lines)))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW!r} host event")
    w0, w1 = window
    busy, modules, gaps = [], {}, []
    for i, (pname, lines) in enumerate(sorted(devices)):
        ops = _merge(_clip([(s, s + d) for _, s, d in
                            lines.get("XLA Ops", ())], w0, w1))
        if not ops:
            continue
        busy.append(sum(b - a for a, b in ops))
        for name, s, d in lines.get("XLA Modules", ()):
            if w0 <= s < w1:
                m = modules.setdefault(module_name(name),
                                       {"count": 0, "seconds": 0.0})
                m["count"] += 1
                m["seconds"] += d * 1e-9
        if not gaps:
            edges = [w0] + [x for ab in ops for x in ab] + [w1]
            gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    host_events.sort(key=lambda ev: ev[1])
    starts = [s for _, s, _ in host_events]
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n_top]:
        best, over = "no host event", 0.0
        # events that began over a second earlier span the whole run
        for name, s, e in host_events[bisect.bisect_left(starts, a - 1e9):
                                      bisect.bisect_left(starts, b)]:
            o = min(b, e) - max(a, s)
            if o > over:
                best, over = name, o
        named.append([f"host: {best}", (b - a) * 1e-9])
    top = sorted(modules.items(), key=lambda kv: -kv[1]["seconds"])[:n_top]
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy) / len(busy) * 1e-9 if busy else 0.0,
            "modules": modules,
            "breakdown": {"device_ops": [[k, v["seconds"]] for k, v in top],
                          "idle_gaps": named}}


def load(path: Path):
    """The planes of one ``.xplane.pb`` file as plain tuples."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return [(p.name, [(ln.name, [(e.name, e.start_ns, e.duration_ns)
                                 for e in ln.events]) for ln in p.lines])
            for p in pd.planes]


def reduce_dir(trace_dir: Path, n_top: int = 10) -> Dict:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    return reduce(load(files[-1]), n_top)
