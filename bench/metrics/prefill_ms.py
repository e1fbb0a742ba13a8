"""Device time per call of the compiled prefill (module ``jit_prefill``
in the trace), in milliseconds."""


def read(ctx):
    m = ctx["trace"]["modules"].get("jit_prefill")
    return 1e3 * m["seconds"] / m["count"] if m else None
