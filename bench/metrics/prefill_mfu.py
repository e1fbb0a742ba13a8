"""FLOPs of the prompts prefilled in the window (every layer, and the
head at each prompt's end), over the window's seconds times the chip's
bf16 peak: the prefill's share of the whole chip, which still reads when
the compiled prefill goes by another name."""

from bench import costs


def read(ctx):
    w = ctx["window"]
    flops = costs.window_flops(ctx["config"], ctx["requests"], w["t0"],
                               w["t1"], decode=False)
    return 100.0 * flops / (w["seconds"] * ctx["peaks"]["bf16_flops_per_s"]) \
        if flops else None
