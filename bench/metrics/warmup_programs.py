"""Programs compiled or loaded from the persistent cache before the window:
one per distinct shape of each jitted program and eager op the traffic
uses."""


def read(run):
    return run.warm_programs
