"""Hotspot: thermal-simulation stencil (Rodinia). Regular access, CPU-init.

Paper roles: Fig. 3 (system > managed in-memory), Fig. 4 timeline shape,
Fig. 6/7 page-size sensitivity, Fig. 11 oversubscription robustness.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.apps.common import KB, AppResult, AppSpec, finish, make_um
from repro.core import Actor, KernelLaunch
from repro.kernels.stencil5 import stencil5

COEFF = 0.1


def run_hotspot(policy_kind: str = "system", *, rows: int = 1024, cols: int = 1024,
                iters: int = 8, page_size: int = 64 * KB,
                oversub_ratio: float = 0.0, auto_migrate: bool = True,
                hw=None, interpret: bool | None = None) -> AppResult:
    nbytes = rows * cols * 4
    um, pol = make_um(policy_kind, page_size=page_size, hw=hw,
                      oversub_ratio=oversub_ratio,
                      app_peak_bytes=3 * nbytes, auto_migrate=auto_migrate)

    with um.phase("alloc"):
        temp_m = um.from_host("temp", (rows, cols), jnp.float32, pol)
        power_m = um.from_host("power", (rows, cols), jnp.float32, pol)
        out_m = um.array("temp_out", (rows, cols), jnp.float32, pol)  # GPU scratch

    key = jax.random.PRNGKey(0)
    with um.phase("cpu_init"):
        temp = 300.0 + 50.0 * jax.random.uniform(key, (rows, cols), jnp.float32)
        power = jax.random.uniform(jax.random.PRNGKey(1), (rows, cols), jnp.float32)
        um.launch("init", writes=[temp_m[:], power_m[:]], actor=Actor.CPU)

    with um.staged(h2d=[temp_m, power_m], d2h=[temp_m]):
        with um.phase("compute"):
            src, dst = temp_m, out_m
            for it in range(iters):
                temp = stencil5(temp, COEFF, interpret=interpret) + 0.001 * power
                # submitted through the batched engine (sync-per-iteration
                # keeps the batch at one launch; charges are identical)
                um.launch_batch([KernelLaunch(
                    f"sweep{it}", reads=[src[:], power_m[:]],
                    writes=[dst[:]],
                    flops=7.0 * rows * cols, actor=Actor.GPU)])
                um.sync()
                src, dst = dst, src

    with um.phase("dealloc"):
        um.free_live()

    return finish(um, "hotspot", policy_kind, page_size, float(jnp.mean(temp)),
                  iters=iters, rows=rows, cols=cols)


SPEC = AppSpec(
    name="hotspot", run=run_hotspot, init_actor="cpu",
    sizes={"fig3": dict(rows=1024, cols=1024, iters=8),
           "fig11": dict(rows=1024, cols=1024, iters=6),
           "small": dict(rows=256, cols=256, iters=6)})
