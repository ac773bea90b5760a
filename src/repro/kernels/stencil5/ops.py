"""jit'd wrapper for the 5-point stencil kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.stencil5.stencil5 import stencil5_fwd

# VMEM bytes for the three double-buffered (TH, W) input stripes. The kernel
# computes in f32, and its temporaries and output buffers take about as much
# again, so 6 MiB keeps the whole call inside the 16 MiB scoped-VMEM default
# (4096x4096 f32 compiles at TH=64 and is refused at TH=128).
_STRIPE_BUDGET = 6 << 20


def pick_tile_h(H: int, W: int) -> int:
    """Tallest stripe (the whole grid up to 256 rows, else a power of two
    down to 8) that divides H and whose three double-buffered f32 stripes
    fit ``_STRIPE_BUDGET``."""
    for th in (min(H, 256), 128, 64, 32, 16, 8):
        if H % th == 0 and 3 * 2 * th * W * 4 <= _STRIPE_BUDGET:
            return th
    raise ValueError(f"no stencil stripe of a {H}x{W} grid fits VMEM")


@functools.partial(jax.jit, static_argnames=("coeff", "tile_h", "interpret"))
def stencil5(grid, coeff: float, *, tile_h: int | None = None,
             interpret: bool | None = None):
    """One 5-point stencil sweep with replicated boundaries. grid: (H, W).
    ``tile_h=None`` picks the stripe height from the grid's width."""
    if tile_h is None:
        tile_h = pick_tile_h(*grid.shape)
    return stencil5_fwd(grid, coeff, tile_h=tile_h, interpret=interpret)
