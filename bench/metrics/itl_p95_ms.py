"""95th percentile of every gap between consecutive output tokens of one
request, both stamped inside the window."""
import numpy as np


def samples(run):
    t0, t1 = run.window
    out = []
    for r in run.reqs:
        ts = [t for t in r.token_times if t0 <= t <= t1]
        out += [b - a for a, b in zip(ts, ts[1:])]
    return out


def read(run):
    g = samples(run)
    return float(np.percentile(g, 95)) * 1e3 if g else None
