"""Pallas TPU kernel: batched 4x4 SU(4) gate application to a statevector.

TPU adaptation of the paper's Qiskit-Aer statevector hot loop: amplitudes
are pre-permuted (ops.py) so the two target qubits form the leading axis of
a (4, M) panel — the matmul then runs with M on the 128-lane axis (MXU/VPU
friendly), streaming M-blocks HBM->VMEM. Complex arithmetic is done as four
real matmuls (re/im planes) since TPUs have no native complex dtype.

This kernel is the *memory-throughput* workload of the paper's Fig. 5/8/9:
bytes moved = 2 * 8 * 2^n per gate, FLOPs = 32 * 2^n (AI ~ 2 -> HBM-bound).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import resolve_interpret, tpu_compiler_params


def _kernel(gr_ref, gi_ref, xr_ref, xi_ref, or_ref, oi_ref):
    gr = gr_ref[...]  # (4,4)
    gi = gi_ref[...]
    xr = xr_ref[...]  # (4, BM)
    xi = xi_ref[...]
    dot = functools.partial(jax.lax.dot_general,
                            dimension_numbers=(((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    or_ref[...] = (dot(gr, xr) - dot(gi, xi)).astype(or_ref.dtype)
    oi_ref[...] = (dot(gr, xi) + dot(gi, xr)).astype(oi_ref.dtype)


def qv_gate_panel(xr, xi, gr, gi, *, block_m: int = 2048,
                  interpret: bool | None = None):
    """xr/xi: (4, M) f32 real/imag amplitude panels; gr/gi: (4,4)."""
    _, M = xr.shape
    block_m = min(block_m, M)
    assert M % block_m == 0, (M, block_m)
    grid = (M // block_m,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((4, 4), lambda m: (0, 0)),
            pl.BlockSpec((4, 4), lambda m: (0, 0)),
            pl.BlockSpec((4, block_m), lambda m: (0, m)),
            pl.BlockSpec((4, block_m), lambda m: (0, m)),
        ],
        out_specs=[
            pl.BlockSpec((4, block_m), lambda m: (0, m)),
            pl.BlockSpec((4, block_m), lambda m: (0, m)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xr.shape, xr.dtype),
            jax.ShapeDtypeStruct(xi.shape, xi.dtype),
        ],
        compiler_params=tpu_compiler_params(("parallel",)),
        interpret=resolve_interpret(interpret),
    )(gr, gi, xr, xi)
