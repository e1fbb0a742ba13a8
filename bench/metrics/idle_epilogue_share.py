"""Share of the traced window in which the chip sat idle while the host's
innermost program span was the epilogue's (``fleet.epilogue``,
``fleet.emit``) or the next step's inputs' (``arena.inputs``): the per-slot
token reads and slicing around the arena step."""

from bench import spans


def read(ctx):
    return spans.idle_share(ctx["trace"], "epilogue")
