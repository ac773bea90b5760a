"""Pure-jnp oracle for paged decode attention."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import NEG_INF


def paged_attention_ref(q, k_pool, v_pool, page_table, lengths, window: int = 0):
    """q: (B,H,D); pools: (P, Hkv, PS, D); page_table: (B, NP) int32;
    lengths: (B,) tokens valid per sequence; window: only the last
    ``window`` of them are attended (0: all). Returns (B,H,D).

    Gathers each sequence's pages then runs masked decode attention (GQA
    block mapping H = Hkv * group).
    """
    B, H, D = q.shape
    P, Hkv, PS, _ = k_pool.shape
    NP = page_table.shape[1]
    group = H // Hkv

    def seq_major(pool):  # (B, NP, Hkv, PS, D) -> (B, Hkv, NP*PS, D)
        kv = pool[page_table].transpose(0, 2, 1, 3, 4)
        return kv.reshape(B, Hkv, NP * PS, D).astype(jnp.float32)

    k, v = seq_major(k_pool), seq_major(v_pool)
    qf = q.astype(jnp.float32).reshape(B, Hkv, group, D)
    logits = jnp.einsum("bngd,bnkd->bngk", qf, k) / jnp.sqrt(float(D))
    pos = jnp.arange(NP * PS)[None, :]
    ok = pos < lengths[:, None]
    if window:
        ok &= pos >= lengths[:, None] - window
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngk,bnkd->bngd", probs, v)
    return out.reshape(B, H, D).astype(q.dtype)
