"""The expert matmuls' least time for the window's routed rows (the larger
of their FLOPs and their bytes against the chip's peaks: the weights of the
experts that received rows and the rows in and out, bench/flops_moe.py)
over the device time of the grouped-matmul operations (``ragged-dot``) in
the trace. Rows and experts hit are the engine's counters over the
window."""
from bench import flops, flops_moe


def read(run):
    if run.trace is None or not run.stats.get("expert_rows"):
        return None
    t = run.trace.matching("ragged-dot", kind="ops")
    if not t:
        return None
    f, b = flops_moe.expert_ffn_cost(run.cfg, run.stats["expert_rows"],
                                     run.stats["experts_hit"], run.q_itemsize)
    least, _ = flops.roofline_seconds(f, b, run.peak)
    return 100.0 * least / t
