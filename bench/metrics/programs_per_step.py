"""Programs the device ran per engine step in the traced window: the
``XLA Modules`` events that start in the window, over the window's steps.
Every jitted program and every eager op on a device array is one."""


def read(run):
    if run.trace is None or not run.steps:
        return None
    a, b = run.trace.window
    devs = run.trace.devices
    n = sum(1 for d in devs for _, s, _ in d.modules if a <= s < b)
    return n / len(devs) / len(run.steps)
