"""Median, over the requests whose first token came inside the window, of
first-token time minus the time the client sent the request."""
import numpy as np


def samples(run):
    t0, t1 = run.window
    return [r.token_times[0] - r.sent for r in run.reqs
            if r.token_times and t0 <= r.token_times[0] <= t1]


def read(run):
    s = samples(run)
    return float(np.median(s)) * 1e3 if s else None
