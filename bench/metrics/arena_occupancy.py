"""Share of the rows an arena call computes that belong to an active
request, over the window: the stepper's counters (arena tokens and masked
rows), read before and after it."""


def read(ctx):
    a = ctx["arena"]
    rows = a["tokens"] + a["masked_rows"]
    return 100.0 * a["tokens"] / rows if rows else None
