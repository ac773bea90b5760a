"""AdamW with fp32 master weights — ZeRO-1-shardable, host-offloadable state.

State layout mirrors the param tree: {'m','v','master'} per leaf + step count.
Sharding is decided at launch time (launch/sharding.py gives optimizer state
an extra 'data'-axis shard — ZeRO-1); the unified-memory integration places
'm'/'v'/'master' on pinned_host when umem decides they are cold (see
launch/sharding.py::offload_opt_specs and DESIGN.md §3.2.2).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp


def adamw_init(params) -> Dict[str, Any]:
    zeros = lambda p: jnp.zeros_like(p, jnp.float32)  # keeps p's sharding
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        # a copy even when params are f32, so params and master never alias
        # (a donated step state may not hold one buffer twice)
        "master": jax.tree.map(
            lambda p: jnp.array(p, jnp.float32, copy=True), params),
        "count": jnp.zeros((), jnp.int32),
    }


def adamw_update(grads, opt, params, *, lr, b1: float = 0.9, b2: float = 0.95,
                 eps: float = 1e-8, weight_decay: float = 0.1,
                 clip_norm: float = 1.0) -> Tuple[Any, Dict[str, Any], jax.Array]:
    """Returns (new_params, new_opt, grad_norm). All grad math in fp32."""
    g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(g32)))
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
    g32 = jax.tree.map(lambda g: g * scale, g32)

    count = opt["count"] + 1
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    def upd(g, m, v, w):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        step = (m / c1) / (jnp.sqrt(v / c2) + eps)
        w = w - lr * (step + weight_decay * w)
        return m, v, w

    flat_g, tdef = jax.tree.flatten(g32)
    flat_m = tdef.flatten_up_to(opt["m"])
    flat_v = tdef.flatten_up_to(opt["v"])
    flat_w = tdef.flatten_up_to(opt["master"])
    out = [upd(g, m, v, w) for g, m, v, w in zip(flat_g, flat_m, flat_v, flat_w)]
    new_m = tdef.unflatten([o[0] for o in out])
    new_v = tdef.unflatten([o[1] for o in out])
    new_w = tdef.unflatten([o[2] for o in out])
    new_params = jax.tree.map(lambda w, p: w.astype(p.dtype), new_w, params)
    new_opt = {"m": new_m, "v": new_v, "master": new_w, "count": count}
    return new_params, new_opt, gnorm
