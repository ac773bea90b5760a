"""Pallas TPU kernel: 5-point stencil over row stripes (hotspot/SRAD).

Halo handling without overlapping BlockSpecs: the grid tiles rows into
(TH, W) stripes and the *same* input array is passed three times with
index_maps i-1 / i / i+1 (clamped at the boundary), so each grid step has
the stripe plus both neighbor stripes resident in VMEM. VMEM footprint =
3*2*TH*W*4 bytes double-buffered — ops.py picks TH from W so this stays
under the VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret, tpu_compiler_params


def _kernel(prev_ref, cur_ref, next_ref, o_ref, *, coeff: float, th: int, nrows: int):
    i = pl.program_id(0)
    c = cur_ref[...].astype(jnp.float32)  # (TH, W)
    # north: last row of prev stripe (clamped: prev==cur at i==0 -> replicate row 0)
    first = jnp.where(i == 0, c[:1], prev_ref[...].astype(jnp.float32)[-1:])
    north = jnp.concatenate([first, c[:-1]], axis=0)
    last = jnp.where(i == nrows - 1, c[-1:], next_ref[...].astype(jnp.float32)[:1])
    south = jnp.concatenate([c[1:], last], axis=0)
    west = jnp.concatenate([c[:, :1], c[:, :-1]], axis=1)
    east = jnp.concatenate([c[:, 1:], c[:, -1:]], axis=1)
    o_ref[...] = (c + coeff * (north + south + east + west - 4.0 * c)).astype(o_ref.dtype)


def stencil5_fwd(grid_in, coeff: float, *, tile_h: int,
                 interpret: bool | None = None):
    H, W = grid_in.shape
    tile_h = min(tile_h, H)
    assert H % tile_h == 0, (H, tile_h)
    n = H // tile_h
    kernel = functools.partial(_kernel, coeff=coeff, th=tile_h, nrows=n)
    spec = lambda off: pl.BlockSpec(
        (tile_h, W), lambda i, _off=off: (jnp.clip(i + _off, 0, n - 1), 0))
    return pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=[spec(-1), spec(0), spec(+1)],
        out_specs=pl.BlockSpec((tile_h, W), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, W), grid_in.dtype),
        compiler_params=tpu_compiler_params(("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(grid_in, grid_in, grid_in)
