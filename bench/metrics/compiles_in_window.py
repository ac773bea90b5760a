"""Programs compiled or loaded from the persistent cache inside the window
(JAX monitoring events); the warm-up should leave none."""


def read(run):
    return sum(s.programs for s in run.steps)
