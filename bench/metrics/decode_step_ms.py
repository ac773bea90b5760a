"""Median wall time of the window's steps that ran a decode batch and no
prefill chunk."""
import numpy as np


def read(run):
    d = [s.t1 - s.t0 for s in run.steps if s.contexts and not s.chunks]
    return float(np.median(d)) * 1e3 if d else None
