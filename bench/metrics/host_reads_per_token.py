"""Blocking device-to-host reads of the decode path (the profiler's
``host_reads``) over the tokens put on the host, both over the window the
profiler was attached for (``ctx["profile"]``: its counters and the
window's ``t0``, ``t1``)."""

from bench.window import window_tokens


def read(ctx):
    p = ctx.get("profile")
    if not p:
        return None
    tokens = sum(1 for _ in window_tokens(ctx["requests"], p["t0"], p["t1"]))
    return p["host_reads"] / tokens if tokens else None
