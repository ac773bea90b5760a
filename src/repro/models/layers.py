"""Shared layer primitives: norms, RoPE, MLPs, inits, run policy."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Run policy: how to execute a forward (chunking, remat, sharding hooks)
# ---------------------------------------------------------------------------


def _no_constrain(x, name: str):
    return x


@dataclass
class RunPolicy:
    """Execution knobs for a forward/step lowering.

    constrain(x, name) inserts sharding constraints (installed by
    launch/sharding.py); names: 'residual', 'logits', 'heads'.
    """

    scan_layers: bool = False
    remat: bool = False
    attn_q_block: int = 0  # 0 => unblocked attention
    attn_kv_block: int = 0
    rwkv_chunk: int = 128
    onehot_embed: bool = False  # TPU-friendly sharded embedding lookup
    constrain: Callable = _no_constrain
    moe_capacity_factor: float = 1.25
    # beyond-paper perf levers (§Perf)
    quantize_tp_collectives: bool = False  # int8 two-phase TP all-reduce
    kv_cache_quant: bool = False  # int8 KV cache (decode memory term)
    moe_impl: str = "dense"  # dense (GShard einsum) | sorted (scatter)
    mesh: Any = None  # set by launch/sharding.make_run_policy

    def c(self, x, name):
        return self.constrain(x, name)


# ---------------------------------------------------------------------------
# Inits
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, in_axis_size: Optional[int] = None):
    fan_in = in_axis_size if in_axis_size is not None else shape[0]
    scale = 1.0 / np.sqrt(max(1, fan_in))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def zeros_init(shape, dtype):
    return jnp.zeros(shape, dtype)


def ones_init(shape, dtype):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------
# Norms (compute in f32, cast back)
# ---------------------------------------------------------------------------


def rmsnorm(x, scale, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def head_rmsnorm(x, scale, eps: float = 1e-6):
    """Per-head qk-norm over head_dim. Affine scale only (keeps zero heads zero)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def apply_norm(kind: str, x, p: Dict[str, Any]):
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


def norm_init(kind: str, d: int, dtype):
    if kind == "rmsnorm":
        return {"scale": ones_init((d,), dtype)}
    return {"scale": ones_init((d,), dtype), "bias": zeros_init((d,), dtype)}


# ---------------------------------------------------------------------------
# Positional embeddings
# ---------------------------------------------------------------------------


def yarn_inv_freq(yarn, head_dim: int, theta: float):
    """YaRN's inverse frequencies (head_dim // 2,) and its attention factor,
    as transformers' ``_compute_yarn_parameters`` computes them (truncated
    correction range, linear ramp), in float64 on the host."""
    half = head_dim // 2
    pos_freqs = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (yarn.factor * pos_freqs)

    def correction_dim(rotations):
        return (head_dim * np.log(yarn.original_max_position_embeddings
                                  / (rotations * 2 * np.pi))
                / (2 * np.log(theta)))

    low = max(np.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(np.ceil(correction_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # as transformers: no singular ramp
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq = interpolation * ramp + extrapolation * (1 - ramp)
    factor = yarn.attention_factor or 0.1 * np.log(yarn.factor) + 1.0
    return inv_freq, float(factor)


def rope_apply(x, positions, theta: float, yarn=None):
    """x: (..., S, H, D); positions broadcastable to (..., S). ``yarn``
    (configs.base.Yarn) scales the frequencies and cos and sin."""
    d = x.shape[-1]
    half = d // 2
    if yarn is None:
        freq = (1.0 / theta) ** (jnp.arange(half, dtype=jnp.float32) / half)
    else:
        inv_freq, mscale = yarn_inv_freq(yarn, d, theta)
        freq = jnp.asarray(inv_freq, jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None:
        cos, sin = cos * mscale, sin * mscale
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_table(positions, d: int):
    """positions: (...,) int -> (..., d) sinusoidal embedding."""
    half = d // 2
    freq = (1.0 / 10_000.0) ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Dense MLPs
# ---------------------------------------------------------------------------


def mlp_init(cfg, key, dtype) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_act in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(ks[0], (d, f), dtype),
            "w_up": dense_init(ks[1], (d, f), dtype),
            "w_down": dense_init(ks[2], (f, d), dtype),
        }
    return {
        "w_up": dense_init(ks[0], (d, f), dtype),
        "b_up": zeros_init((f,), dtype),
        "w_down": dense_init(ks[1], (f, d), dtype),
        "b_down": zeros_init((d,), dtype),
    }


def _down_proj(h, w_down, policy: RunPolicy):
    if policy.quantize_tp_collectives and policy.mesh is not None:
        from jax.sharding import PartitionSpec as P

        from repro.models.qcomm import rowparallel_matmul_q8

        return rowparallel_matmul_q8(
            h, w_down, policy.mesh,
            x_spec=P(None, None, "model"), w_spec=P("model", None),
            out_dtype=h.dtype)
    return h @ w_down


def mlp_apply(cfg, p, x, policy: RunPolicy):
    if cfg.mlp_act == "swiglu":
        g = jax.nn.silu(x @ p["w_gate"])
        h = g * (x @ p["w_up"])
        return _down_proj(h, p["w_down"], policy)
    if cfg.mlp_act == "geglu":
        g = jax.nn.gelu(x @ p["w_gate"], approximate=True)
        h = g * (x @ p["w_up"])
        return _down_proj(h, p["w_down"], policy)
    h = jax.nn.gelu(x @ p["w_up"] + p["b_up"], approximate=True)
    return _down_proj(h, p["w_down"], policy) + p["b_down"]
