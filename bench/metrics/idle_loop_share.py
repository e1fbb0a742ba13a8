"""Share of the traced window in which the chip sat idle while the host's
innermost program span was any other of the event loop's
(``fleet.event.*``, ``fleet.round``, ``fleet.price``, ``arena.dispatch``,
``fleet.retire``)."""

from bench import spans


def read(ctx):
    return spans.idle_share(ctx["trace"], "loop")
