"""The paged decode-attention kernel's least time for the window's decode
work (the larger of its FLOPs and its bytes against the chip's peaks) over
the kernel's device time in the trace. The work counts the tokens each
sequence attended, in the pool's dtype as read at run time."""
from bench import flops


def read(run):
    if run.trace is None:
        return None
    t_kernel = run.trace.matching("paged_attention")
    if not t_kernel:
        return None
    cfg = run.cfg
    f = b = 0
    for s in run.steps:
        if s.contexts:
            sf, sb = flops.paged_attention_cost(
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], s.contexts, run.q_itemsize, run.kv_itemsize)
            f += sf * cfg["num_hidden_layers"]
            b += sb * cfg["num_hidden_layers"]
    least, _ = flops.roofline_seconds(f, b, run.peak)
    return 100.0 * least / t_kernel
