"""Quantized tensor-parallel collectives (beyond-paper, §Perf lever).

Megatron row-parallel projections end in an all-reduce of full activations —
the collective-term bottleneck of 32k-token prefill at TP=16. This module
replaces that all-reduce with an int8 two-phase reduce:

  partial (B,S,d) --quantize--> int8 + per-(token,shard-block) scales
    --all_to_all--> dequant-sum of my d-shard --quantize-->
    --all_gather--> dequant -> full (B,S,d)

Wire bytes/device: ~2*(n-1)/n * E * 1B vs 2*(n-1)/n * E * 2B for the bf16
all-reduce -> ~2x reduction (plus f32 scales, ~d/(d/n)/4 overhead). Intended
for inference lowerings (prefill/decode); rounding is not differentiated.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quant_blocks(y, n: int):
    """y: (..., n, m) f32 -> int8 codes + per-(..., n) scales."""
    scale = jnp.max(jnp.abs(y), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(y / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def quantized_allreduce(y, axis_name: str):
    """int8 two-phase all-reduce along a mesh axis. y: (B,S,d) f32/bf16 partial."""
    n = jax.lax.axis_size(axis_name)
    B, S, d = y.shape
    assert d % n == 0, (d, n)
    y4 = y.astype(jnp.float32).reshape(B, S, n, d // n)
    q, s = _quant_blocks(y4, n)
    # exchange: piece j of every device lands on device j
    q = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=2, tiled=False)
    s = jax.lax.all_to_all(s, axis_name, split_axis=2, concat_axis=2, tiled=False)
    part = jnp.sum(q.astype(jnp.float32) * s, axis=2)  # (B,S,d/n): my shard, reduced
    q2, s2 = _quant_blocks(part[..., None, :], 1)
    q2 = q2[..., 0, :]
    s2 = s2[..., 0, :]
    qg = jax.lax.all_gather(q2, axis_name, axis=2, tiled=False)  # (B,S,n,d/n)
    sg = jax.lax.all_gather(s2, axis_name, axis=2, tiled=False)
    out = (qg.astype(jnp.float32) * sg[..., None].reshape(B, S, n, 1)).reshape(B, S, d)
    return out


def rowparallel_matmul_q8(x_sharded_contract, w, mesh, *, x_spec: P, w_spec: P,
                          out_dtype):
    """shard_map'd row-parallel projection with the quantized all-reduce.

    x: (B,S,K) with K sharded over 'model'; w: (K, d) sharded on K.
    Returns (B,S,d) replicated over 'model'.
    """
    def f(x_loc, w_loc):
        y_part = jnp.einsum("bsk,kd->bsd", x_loc, w_loc,
                            preferred_element_type=jnp.float32)
        return quantized_allreduce(y_part, "model").astype(out_dtype)

    return jax.shard_map(f, mesh=mesh, in_specs=(x_spec, w_spec),
                         out_specs=P(*([None] * 3)), check_vma=False)(
        x_sharded_contract, w)
