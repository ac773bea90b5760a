"""jit'd wrapper: arbitrary-qubit-pair gate application via permute + kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.qv_gate.qv_gate import qv_gate_panel


@functools.partial(jax.jit, static_argnames=("q1", "q2", "n_qubits", "interpret"))
def apply_two_qubit_gate(state, gate, q1: int, q2: int, n_qubits: int,
                         *, interpret: bool | None = None):
    """state: (2**n,) complex64; gate: (4,4) complex64. Returns new state."""
    psi = state.reshape((2,) * n_qubits)
    a1, a2 = n_qubits - 1 - q1, n_qubits - 1 - q2
    psi = jnp.moveaxis(psi, (a1, a2), (0, 1)).reshape(4, -1)
    xr, xi = jnp.real(psi).astype(jnp.float32), jnp.imag(psi).astype(jnp.float32)
    gr, gi = jnp.real(gate).astype(jnp.float32), jnp.imag(gate).astype(jnp.float32)
    yr, yi = qv_gate_panel(xr, xi, gr, gi, interpret=interpret)
    out = (yr + 1j * yi).astype(state.dtype)
    out = out.reshape((2, 2) + (2,) * (n_qubits - 2))
    out = jnp.moveaxis(out, (0, 1), (a1, a2))
    return out.reshape(-1)
