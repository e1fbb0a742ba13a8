"""Model FLOPs the window required (``costs.window_flops``: prompts
prefilled and tokens decoded in it, each through its exit's layers, head
included), over the window's seconds times the chip's bf16 peak."""

from bench import costs


def read(ctx):
    w = ctx["window"]
    flops = costs.window_flops(ctx["config"], ctx["requests"], w["t0"],
                               w["t1"])
    return 100.0 * flops / (w["seconds"] * ctx["peaks"]["bf16_flops_per_s"]) \
        if flops else None
