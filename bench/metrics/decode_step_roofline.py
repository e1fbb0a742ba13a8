"""The arena step's least time over its measured device time.

The least time of the window's calls is the larger of their operations
over the peak and their bytes over the memory bandwidth, with the
operations and bytes of what the window decoded: each call reads the
weights of its layers once (counted at the shallowest exit the window
used, so never over), and each token attends to the cached keys and
values of its own request up to its position and writes its own.  Both
sides are taken per call: the least time over the stepper's count of
calls in the window, the device time over the trace's count of
``jit_astep`` modules."""

from bench import costs, weights
from bench.window import window_tokens


def read(ctx):
    m = ctx["trace"]["modules"].get("jit_astep")
    calls = ctx["arena"]["calls"]
    if not m or not calls:
        return None
    cfg, w = ctx["config"], ctx["window"]
    depth = weights.exit_layers(cfg) + [cfg["num_hidden_layers"]]
    toks = list(window_tokens(ctx["requests"], w["t0"], w["t1"]))
    if not toks:
        return None
    shallow = min(depth[r.exit_point - 1] for r, _ in toks)
    flops = nbytes = 0
    for r, i in toks:
        n, pos = depth[r.exit_point - 1], r.prompt_len + i
        flops += costs.decode_flops(cfg, n, pos, head=False)
        nbytes += (pos + 1) * costs.kv_bytes_per_position(cfg, n)
    nbytes += calls * costs.weight_bytes(cfg, shallow)
    least, _ = costs.least_seconds(flops, nbytes, ctx["peaks"])
    return 100.0 * (least / calls) / (m["seconds"] / m["count"])
