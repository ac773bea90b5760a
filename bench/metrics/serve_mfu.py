"""Model FLOPs of the window (every prompt position prefilled and every
token decoded, with attention over its context and the logits the engine
computes) per wall second, over the chip's bf16 peak."""
from bench import flops


def read(run):
    cfg = run.cfg
    total = 0
    for s in run.steps:
        total += sum(flops.prefill_flops(cfg, a, b, last) for a, b, last in s.chunks)
        total += flops.decode_flops(cfg, s.contexts)
    return 100.0 * total / run.window_s / run.peak["bf16_flops_per_s"]
