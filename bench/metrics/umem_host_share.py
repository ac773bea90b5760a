"""Share of the window's wall seconds the host spent inside the memory
model's calls (launch, launch_batch, sync, prefetch_async, demote)."""


def read(run):
    return 100.0 * run.umem_s / run.window_s
