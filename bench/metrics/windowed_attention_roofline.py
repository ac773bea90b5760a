"""The paged decode-attention kernel's least time for the window's decode
work over its device time (``jit_paged_attention`` in the trace), with
``min(context, sliding_window)`` keys a token in sliding layers and
``context`` in full ones, in the pool's dtype as read at run time."""
from bench import flops, flops_moe


def read(run):
    if run.trace is None:
        return None
    t_kernel = run.trace.matching("paged_attention")
    if not t_kernel:
        return None
    f = b = 0
    for s in run.steps:
        if s.contexts:
            sf, sb = flops_moe.windowed_attention_cost(
                run.cfg, s.contexts, run.q_itemsize, run.kv_itemsize)
            f, b = f + sf, b + sb
    least, _ = flops.roofline_seconds(f, b, run.peak)
    return 100.0 * least / t_kernel
