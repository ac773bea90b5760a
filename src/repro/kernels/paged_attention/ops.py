"""jit'd public wrapper for paged decode attention."""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.paged_attention import paged_attention_fwd


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(q, k_pool, v_pool, page_table, lengths, *, window: int = 0,
                    interpret: bool | None = None):
    """Decode attention over a paged KV cache.

    q: (B,H,D); k_pool/v_pool: (P, Hkv, PS, D); page_table: (B, NP) int32
    (page ids per sequence, in order); lengths: (B,) valid tokens; window:
    the last ``window`` of them attended (0: all).
    """
    return paged_attention_fwd(q, k_pool, v_pool, page_table, lengths,
                               window=window, interpret=interpret)
