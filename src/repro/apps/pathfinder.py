"""Pathfinder: 2-D grid dynamic programming (Rodinia). Regular, CPU-init."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.apps.common import KB, AppResult, AppSpec, finish, make_um
from repro.core import Actor, KernelLaunch


def _dp_all_rows(data):
    """min-path DP: cost[j] = data[i,j] + min(prev[j-1], prev[j], prev[j+1])."""

    def step(prev, row):
        left = jnp.concatenate([prev[:1], prev[:-1]])
        right = jnp.concatenate([prev[1:], prev[-1:]])
        cur = row + jnp.minimum(prev, jnp.minimum(left, right))
        return cur, None

    out, _ = jax.lax.scan(step, data[0].astype(jnp.int32), data[1:])
    return out


def run_pathfinder(policy_kind: str = "system", *, rows: int = 4096, cols: int = 1024,
                   page_size: int = 64 * KB, rows_per_kernel: int = 512,
                   oversub_ratio: float = 0.0, auto_migrate: bool = True,
                   hw=None, interpret: bool | None = None) -> AppResult:
    row_bytes = cols * 4
    um, pol = make_um(policy_kind, page_size=page_size, hw=hw,
                      oversub_ratio=oversub_ratio,
                      app_peak_bytes=rows * row_bytes + 2 * row_bytes,
                      auto_migrate=auto_migrate)

    with um.phase("alloc"):
        wall = um.from_host("wall", (rows, cols), jnp.int32, pol)
        res = um.array("result", (2, cols), jnp.int32, pol)  # prev/cur row pair

    key = jax.random.PRNGKey(3)
    with um.phase("cpu_init"):
        data = jax.random.randint(key, (rows, cols), 0, 10, jnp.int32)
        um.launch("init", writes=[wall[:]], actor=Actor.CPU)

    with um.staged(h2d=[wall], d2h=[res.rows(0, 1)]):
        with um.phase("compute"):
            result = _dp_all_rows(data)
            # model the row-sweep: one kernel per block of rows, streaming the wall
            for r0 in range(0, rows, rows_per_kernel):
                r1 = min(r0 + rows_per_kernel, rows)
                um.launch_batch([KernelLaunch(
                    f"rows{r0}",
                    reads=[wall.rows(r0, r1), res.rows(0, 1)],
                    writes=[res.rows(1, 2)],
                    flops=5.0 * (r1 - r0) * cols, actor=Actor.GPU)])
                um.sync()

    with um.phase("dealloc"):
        um.free_live()

    return finish(um, "pathfinder", policy_kind, page_size,
                  float(jnp.sum(result) % 1_000_003), rows=rows, cols=cols)


SPEC = AppSpec(
    name="pathfinder", run=run_pathfinder, init_actor="cpu",
    sizes={"fig3": dict(rows=2048, cols=512),
           "fig11": dict(rows=2048, cols=512),
           "small": dict(rows=1024, cols=256)})
