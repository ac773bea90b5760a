"""Model FLOPs of the window (every prompt position prefilled and every
token decoded: attention projections, windowed attention over the context,
the router, the experts each token is routed to, and the logits the engine
computes) per wall second, over the chip's bf16 peak."""
from bench import flops_moe


def read(run):
    cfg = run.cfg
    total = 0
    for s in run.steps:
        total += sum(flops_moe.prefill_flops(cfg, a, b, last)
                     for a, b, last in s.chunks)
        total += flops_moe.decode_flops(cfg, s.contexts)
    return 100.0 * total / run.window_s / run.peak["bf16_flops_per_s"]
