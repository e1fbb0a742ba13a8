"""Share of the traced window in which the chip sat idle while the host's
innermost program span was admission's (``fleet.admit``,
``fleet.prefill``, ``arena.scatter``)."""

from bench import spans


def read(ctx):
    return spans.idle_share(ctx["trace"], "admit")
