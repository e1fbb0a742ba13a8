"""The prefill's least time over its measured device time: the larger of
its operations over the peak and its bytes over the memory bandwidth
(``costs.prefill_flops`` without the head, which runs outside the
compiled prefill, and ``costs.prefill_bytes``), averaged over the
requests prefilled in the window, against the mean device time of a
``jit_prefill`` module in the trace."""

from bench import costs
from bench.window import window_prefills


def read(ctx):
    m = ctx["trace"]["modules"].get("jit_prefill")
    w = ctx["window"]
    reqs = window_prefills(ctx["requests"], w["t0"], w["t1"])
    if not m or not reqs:
        return None
    cfg = ctx["config"]
    least = sum(costs.least_seconds(
        costs.prefill_flops(cfg, r.prompt_len, head=False),
        costs.prefill_bytes(cfg, r.prompt_len), ctx["peaks"])[0]
        for r in reqs) / len(reqs)
    return 100.0 * least / (m["seconds"] / m["count"])
