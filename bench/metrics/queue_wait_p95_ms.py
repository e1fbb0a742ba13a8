"""95th percentile, in milliseconds, of the profiler's queue waits (the
engine taking a request's arrival -> the start of its admission) whose
admission fell in the window the profiler was attached for."""

from bench.window import percentile


def read(ctx):
    p = ctx.get("profile")
    if not p:
        return None
    waits = [w for t, w in p["queue_waits"] if p["t0"] <= t < p["t1"]]
    q = percentile(waits, 95)
    return None if q is None else 1e3 * q
