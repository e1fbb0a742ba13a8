"""Device time per call of the compiled arena step (module ``jit_astep``
in the trace), in milliseconds."""


def read(ctx):
    m = ctx["trace"]["modules"].get("jit_astep")
    return 1e3 * m["seconds"] / m["count"] if m else None
