"""Kernel micro-benches (interpret mode on CPU: structural timing only —
real perf comes from the §Roofline analysis, not CPU wall time)."""
import time

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.qv_gate import apply_two_qubit_gate
from repro.kernels.stencil5 import stencil5

from benchmarks.common import emit


def _bench(fn, *args, n=3, **kw):
    fn(*args, **kw)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*args, **kw)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n * 1e6


def run():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 256, 8, 64), jnp.float32)
    k = jax.random.normal(key, (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(key, (1, 256, 2, 64), jnp.float32)
    emit("kernel/flash_attention_256", _bench(
        flash_attention, q, k, v, block_q=64, block_k=64, interpret=True),
        "B1_S256_H8_D64")
    qd = jax.random.normal(key, (2, 8, 64), jnp.float32)
    kp = jax.random.normal(key, (16, 2, 16, 64), jnp.float32)  # (P, Hkv, PS, D)
    pt = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    ln = jnp.asarray([60, 33], jnp.int32)
    emit("kernel/paged_attention", _bench(
        paged_attention, qd, kp, kp, pt, ln, interpret=True), "B2_NP4_PS16")
    st = jnp.zeros((2 ** 14,), jnp.complex64).at[0].set(1.0)
    g = jnp.eye(4, dtype=jnp.complex64)
    emit("kernel/qv_gate_14q", _bench(
        apply_two_qubit_gate, st, g, 3, 9, 14, interpret=True), "n14")
    grid = jax.random.normal(key, (512, 256), jnp.float32)
    emit("kernel/stencil5_512x256", _bench(
        stencil5, grid, 0.1, tile_h=128, interpret=True), "")
