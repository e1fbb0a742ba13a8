"""Stamps taken around the program's public objects, and the window's
arithmetic over them.

A :class:`Clock` hands out host-clock stamps and ends a run: once a stamp
falls at or after its deadline it raises :class:`WindowClosed`, which
unwinds the engine's event loop, so no chip time is spent after the
window.  Requests (see ``harness.stamped_request_class``) stamp the moment
the engine takes their arrival (their first assignment to an edge) and
the moment each token is on the host (its append to ``tokens``).
"""
from __future__ import annotations

import math
import time
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np


class WindowClosed(Exception):
    """A stamp fell at or after the window's end."""


class Clock:
    """Stamps on ``time.perf_counter``; ``deadline`` ends the run.
    ``on_token`` (optional) is told of every token stamp; it may raise to
    end a run early (the warm-up does).  ``on_mark`` is called once, at
    the first stamp at or after ``mark`` (the traced slice's end)."""

    def __init__(self):
        self.deadline = self.mark = math.inf
        self.on_token = self.on_mark = None

    def now(self) -> float:
        t = time.perf_counter()
        if t >= self.mark:
            self.mark = math.inf
            self.on_mark()
        if t >= self.deadline:
            raise WindowClosed
        return t


class StampList(list):
    """A request's token list: each append is stamped first, so a token
    that would land after the window is never appended."""

    def __init__(self, owner, items: Iterable = ()):
        super().__init__(items)
        self.owner = owner
        self.stamps: List[float] = []

    def append(self, tok):
        t = self.owner.clock.now()
        super().append(tok)
        self.stamps.append(t)
        if self.owner.clock.on_token is not None:
            self.owner.clock.on_token(self.owner)


def tokens_in(stamps: Sequence[float], t0: float, t1: float) -> int:
    return sum(1 for t in stamps if t0 <= t < t1)


def ttft_samples(reqs, t0: float, t1: float) -> List[float]:
    """Seconds from the engine taking each request's arrival to its first
    token on the host, for every request whose first token fell in
    [t0, t1)."""
    return [r.tokens.stamps[0] - r.arrival_wall for r in reqs
            if r.arrival_wall is not None and r.tokens.stamps
            and t0 <= r.tokens.stamps[0] < t1]


def itl_samples(reqs, t0: float, t1: float) -> List[float]:
    """Seconds between consecutive tokens of a request, for every pair
    whose two tokens fell in [t0, t1)."""
    out = []
    for r in reqs:
        s = [t for t in r.tokens.stamps if t0 <= t < t1]
        out += [b - a for a, b in zip(s[:-1], s[1:])]
    return out


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile over all samples (numpy's linear rule);
    ``None`` without samples."""
    return float(np.percentile(np.asarray(values, float), q)) \
        if len(values) else None


def summary(reqs, t0: float, t1: float) -> Dict:
    """The end-to-end readings of one window over edge-served requests."""
    edge = [r for r in reqs if r.arrival_wall is not None]
    tokens = sum(tokens_in(r.tokens.stamps, t0, t1) for r in edge)
    ttft, itl = ttft_samples(edge, t0, t1), itl_samples(edge, t0, t1)
    return {"tokens": tokens, "tokens_per_s": tokens / (t1 - t0),
            "ttft_n": len(ttft), "itl_n": len(itl),
            **{f"ttft_p{q}_ms": _ms(percentile(ttft, q))
               for q in (50, 90, 95, 99)},
            **{f"itl_p{q}_ms": _ms(percentile(itl, q))
               for q in (50, 90, 95, 99)}}


def _ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else 1e3 * x


def window_tokens(reqs, t0: float, t1: float):
    """``(request, index)`` of every token put on the host in [t0, t1)
    by an edge-served request (index 0 is the first decoded token)."""
    for r in reqs:
        if r.arrival_wall is None:
            continue
        for i, t in enumerate(r.tokens.stamps):
            if t0 <= t < t1:
                yield r, i


def window_prefills(reqs, t0: float, t1: float):
    """Edge-served requests whose first token fell in [t0, t1): their
    prefill ran in the same round, inside the window."""
    return [r for r in reqs if r.arrival_wall is not None
            and r.tokens.stamps and t0 <= r.tokens.stamps[0] < t1]
