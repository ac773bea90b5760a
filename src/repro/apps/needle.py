"""Needleman-Wunsch sequence alignment (Rodinia). Irregular, CPU-init.

Anti-diagonal wavefront DP; the row-associative form lets JAX compute each
row with a cummax instead of a serial column loop (see _nw_rows)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.apps.common import KB, AppResult, AppSpec, finish, make_um
from repro.core import Actor, KernelLaunch


def _nw_rows(sim, penalty: int):
    """F[i,j] = max(F[i-1,j-1]+sim, F[i-1,j]-p, F[i,j-1]-p).

    Per-row: A[j] = max(F[i-1,j-1]+sim[i,j], F[i-1,j]-p);
    F[i,j] = cummax_j(A[j] + p*j) - p*j   (max-plus prefix identity).
    """
    n = sim.shape[1]
    jdx = jnp.arange(n, dtype=jnp.int32) * penalty

    def step(prev, srow):
        shifted = jnp.concatenate([jnp.array([-penalty], prev.dtype), prev[:-1]])
        A = jnp.maximum(shifted + srow, prev - penalty)
        F = jax.lax.cummax(A + jdx) - jdx
        return F, None

    init = -penalty * jnp.arange(n, dtype=jnp.int32)
    last, _ = jax.lax.scan(step, init, sim)
    return last


def run_needle(policy_kind: str = "system", *, n: int = 2048, penalty: int = 1,
               page_size: int = 64 * KB, waves_per_kernel: int = 64,
               oversub_ratio: float = 0.0, auto_migrate: bool = True,
               hw=None, interpret: bool | None = None) -> AppResult:
    nbytes = n * n * 4
    um, pol = make_um(policy_kind, page_size=page_size, hw=hw,
                      oversub_ratio=oversub_ratio,
                      app_peak_bytes=2 * nbytes, auto_migrate=auto_migrate)

    with um.phase("alloc"):
        ref = um.from_host("reference", (n, n), jnp.int32, pol)
        mat = um.from_host("matrix", (n, n), jnp.int32, pol)

    key = jax.random.PRNGKey(11)
    with um.phase("cpu_init"):
        sim = jax.random.randint(key, (n, n), -2, 3, jnp.int32)
        um.launch("init", writes=[ref[:], mat[:]], actor=Actor.CPU)

    with um.staged(h2d=[ref, mat], d2h=[mat]):
        with um.phase("compute"):
            last_row = _nw_rows(sim, penalty)
            # wavefront sweeps touch growing/shrinking diagonal bands: model as
            # strided sub-range kernels (irregular pattern)
            waves = 2 * n - 1
            for w0 in range(0, waves, waves_per_kernel):
                w1 = min(w0 + waves_per_kernel, waves)
                frac0, frac1 = w0 / waves, w1 / waves
                lo = int(frac0 * nbytes) // 4096 * 4096
                hi = max(lo + 4096, int(frac1 * nbytes) // 4096 * 4096)
                hi = min(hi, nbytes)
                um.launch_batch([KernelLaunch(
                    f"wave{w0}",
                    reads=[ref.byterange(lo, hi), mat.byterange(lo, hi)],
                    writes=[mat.byterange(lo, hi)],
                    flops=10.0 * (hi - lo) / 4, actor=Actor.GPU)])
                um.sync()

    with um.phase("dealloc"):
        um.free_live()

    return finish(um, "needle", policy_kind, page_size,
                  float(last_row[-1]), n=n)


SPEC = AppSpec(
    name="needle", run=run_needle, init_actor="cpu",
    sizes={"fig3": dict(n=1024),
           "fig11": dict(n=1024),
           "small": dict(n=512)})
