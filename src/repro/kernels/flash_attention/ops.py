"""jit'd public wrapper for the flash attention kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_fwd


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q: (B,S,H,D); k,v: (B,S,Hkv,D) -> (B,S,H,D). Layout-matches models/attention."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o = flash_attention_fwd(qt, kt, vt, causal=causal, window=window,
                            block_q=block_q, block_k=block_k, interpret=interpret)
    return jnp.swapaxes(o, 1, 2)
