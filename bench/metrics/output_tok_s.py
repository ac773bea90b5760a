"""Output tokens produced in the window over the window's wall seconds."""


def read(run):
    return sum(s.tokens for s in run.steps) / run.window_s
