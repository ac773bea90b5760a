"""Attention: GQA with exact TP head layout, full / blocked / decode paths.

Full path:    one (Sq x Sk) logits tensor per kv-group     (train_4k)
Blocked path: block-causal online-softmax, python-unrolled  (prefill_32k;
              only lower-triangular blocks are emitted, so compiled FLOPs
              track the causal S^2/2 and live buffers stay block-sized)
Decode path:  one query token against a dense KV cache      (decode_32k)
Sliding-window (local) attention reuses all three with a window mask and a
ring-buffer cache for decode.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import RunPolicy, dense_init, head_rmsnorm, ones_init, rope_apply, zeros_init
from repro.models.layout import HeadLayout

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attn_init(cfg, layout: HeadLayout, key, dtype) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    wq = dense_init(ks[0], (d, layout.n_q, hd), dtype, in_axis_size=d)
    wk = dense_init(ks[1], (d, layout.n_kv, hd), dtype, in_axis_size=d)
    wv = dense_init(ks[2], (d, layout.n_kv, hd), dtype, in_axis_size=d)
    wo = dense_init(ks[3], (layout.n_q, hd, d), dtype, in_axis_size=layout.n_q * hd)
    p = {
        "wq": layout.expand_q(wq, 1),
        "wk": layout.expand_kv(wk, 1),
        "wv": layout.expand_kv(wv, 1),
        "wo": layout.expand_q(wo, 0),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros_init((layout.n_q_eff, hd), dtype)
        p["bk"] = zeros_init((layout.n_kv_eff, hd), dtype)
        p["bv"] = zeros_init((layout.n_kv_eff, hd), dtype)
    if cfg.qk_norm:
        p["q_norm"] = ones_init((hd,), dtype)
        p["k_norm"] = ones_init((hd,), dtype)
    return p


def _project_qkv(cfg, p, x, layout: HeadLayout, positions, yarn=None):
    """x: (B,S,d) -> q (B,S,N,P,D), k,v (B,S,N,D); RoPE (``yarn``-scaled
    where given) applied."""
    B, S, _ = x.shape
    N, P, D = layout.n_kv_eff, layout.p, cfg.head_dim
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"], preferred_element_type=jnp.float32)
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"], preferred_element_type=jnp.float32)
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"], preferred_element_type=jnp.float32)
    q, k, v = q.astype(x.dtype), k.astype(x.dtype), v.astype(x.dtype)
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = head_rmsnorm(q, p["q_norm"])
        k = head_rmsnorm(k, p["k_norm"])
    if cfg.pos_emb == "rope":
        q = rope_apply(q, positions, cfg.rope_theta, yarn)
        k = rope_apply(k, positions, cfg.rope_theta, yarn)
    q = q.reshape(B, S, N, P, D)
    return q, k, v


def _out_proj(p, o, layout: HeadLayout, policy: Optional[RunPolicy] = None):
    # bf16 contraction: the row-parallel TP all-reduce then runs in bf16
    # (Megatron practice — halves wire bytes and collective buffer size)
    B, S = o.shape[:2]
    hd = p["wo"].shape[1]
    o = o.reshape(B, S, layout.n_q_eff * hd)
    if (policy is not None and policy.quantize_tp_collectives
            and policy.mesh is not None):
        from jax.sharding import PartitionSpec as P

        from repro.models.qcomm import rowparallel_matmul_q8

        w = p["wo"].reshape(layout.n_q_eff * hd, -1)
        return rowparallel_matmul_q8(
            o, w, policy.mesh,
            x_spec=P(None, None, "model"), w_spec=P("model", None),
            out_dtype=o.dtype)
    o = o.reshape(B, S, layout.n_q_eff, hd)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"])


# ---------------------------------------------------------------------------
# Core scaled-dot-product over grouped heads
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, bias):
    """q (B,Sq,N,P,D); k,v (B,Sk,N,D); bias broadcastable to (B,N,P,Sq,Sk)."""
    D = q.shape[-1]
    logits = jnp.einsum("bqnpd,bknd->bnpqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * (1.0 / math.sqrt(D)) + bias
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bnpqk,bknd->bqnpd", probs.astype(v.dtype), v)
    return out


def _causal_bias(qpos, kpos, window: int):
    """Additive mask from absolute positions. qpos (Sq,)|(B,Sq); kpos (Sk,)|(B,Sk)."""
    if qpos.ndim == 1:
        qpos, kpos = qpos[:, None], kpos[None, :]
        expand = (1, 1, 1)
    else:
        qpos, kpos = qpos[:, :, None], kpos[:, None, :]
        expand = None
    ok = kpos <= qpos
    if window > 0:
        ok &= kpos > qpos - window
    ok &= kpos >= 0  # ring-buffer slots not yet written
    bias = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
    if expand is not None:
        return bias[None, None, None]  # (1,1,1,Sq,Sk)
    return bias[:, None, None]  # (B,1,1,Sq,Sk)


# ---------------------------------------------------------------------------
# Full attention (train_4k) — also returns KV for cache building
# ---------------------------------------------------------------------------


def attn_apply(cfg, p, x, layout: HeadLayout, policy: RunPolicy, *, window: int = 0,
               positions=None, yarn=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = _project_qkv(cfg, p, x, layout, positions, yarn)
    qb = policy.attn_q_block
    if qb and S > qb:
        o = _blocked_causal(q, k, v, qb, policy.attn_kv_block or qb, window)
    else:
        bias = _causal_bias(jnp.arange(S), jnp.arange(S), window)
        o = _sdpa(q, k, v, bias)
    return _out_proj(p, o, layout, policy), {"k": k, "v": v}


def _blocked_causal(q, k, v, QB: int, KB: int, window: int):
    """Python-unrolled block-causal online-softmax attention.

    Only blocks intersecting the causal (and window) band are emitted, so the
    lowered HLO carries ~S^2/2 logits FLOPs and O(QB*KB) live buffers. All
    einsums run head-major ((B,N,P,S,D) x (B,N,S,D)) so they lower to plain
    batched dot_generals — no materialized transpose copies of (QB,KB)
    buffers — and a zero-cost data dependency on the online-softmax carry
    serializes pairs so only one logits buffer is live at a time.
    """
    B, S, N, P, D = q.shape
    assert S % QB == 0 and S % KB == 0, (S, QB, KB)
    nq, nk = S // QB, S // KB
    scale = 1.0 / math.sqrt(D)
    qh = jnp.moveaxis(q, 1, 3)  # (B,N,P,S,D)
    kh = jnp.moveaxis(k, 1, 2)  # (B,N,S,D)
    vh = jnp.moveaxis(v, 1, 2)
    outs = []
    chain = jnp.zeros((), jnp.float32)  # serializes q-blocks
    for i in range(nq):
        qi = qh[:, :, :, i * QB : (i + 1) * QB]
        m = jnp.full((B, N, P, QB), NEG_INF, jnp.float32) + chain
        l = jnp.zeros((B, N, P, QB), jnp.float32)
        acc = jnp.zeros((B, N, P, QB, D), jnp.float32)
        q_lo, q_hi = i * QB, (i + 1) * QB - 1
        for j in range(nk):
            k_lo, k_hi = j * KB, (j + 1) * KB - 1
            if k_lo > q_hi:  # fully future
                continue
            if window > 0 and k_hi <= q_lo - window:  # fully out of window
                continue
            kj = kh[:, :, k_lo : k_lo + KB]
            vj = vh[:, :, k_lo : k_lo + KB]
            # data-dependency on the carry: stops XLA hoisting every pair's
            # logits matmul (one live (QB,KB) buffer instead of all pairs)
            kj = kj + (m[0, 0, 0, 0] * 0.0).astype(kj.dtype)
            logits = jnp.einsum("bnpqd,bnkd->bnpqk", qi, kj,
                                preferred_element_type=jnp.float32) * scale
            full_inside = k_hi <= q_lo and (window == 0 or k_lo > q_hi - window)
            if not full_inside:
                qpos = jnp.arange(q_lo, q_hi + 1)
                kpos = jnp.arange(k_lo, k_hi + 1)
                logits = logits + _causal_bias(qpos, kpos, window)[0]
            m_new = jnp.maximum(m, logits.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(logits - m_new[..., None])
            l = l * alpha + pr.sum(axis=-1)
            acc = acc * alpha[..., None]
            acc = acc + jnp.einsum("bnpqk,bnkd->bnpqd", pr.astype(v.dtype), vj,
                                   preferred_element_type=jnp.float32)
            m = m_new
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        outs.append(o.astype(q.dtype))
        chain = o[0, 0, 0, 0, 0].astype(jnp.float32) * 0.0
    out = jnp.concatenate(outs, axis=3)  # (B,N,P,S,D)
    return jnp.moveaxis(out, 3, 1)  # (B,S,N,P,D)


# ---------------------------------------------------------------------------
# Decode (one token, dense or ring-buffer cache)
# ---------------------------------------------------------------------------


def _quant_heads(t):
    """t: (B,1,N,D) -> (int8 codes, f32 scales (B,1,N,1))."""
    s = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / s), -127, 127).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def attn_decode(cfg, p, x, layout: HeadLayout, policy: RunPolicy, pos, cache,
                *, window: int = 0, yarn=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B,1,d); pos: (B,) absolute position of the new token.

    cache: {'k','v'}: (B, S, N, D) dense, or (B, W, N, D) ring when window>0.
    int8-quantized cache adds {'ks','vs'} per-(token,head) scales (the decode
    memory-term lever — halves HBM bytes per step).
    """
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(cfg, p, x, layout, pos[:, None], yarn)
    quant = "ks" in cache
    if quant:
        k_w, ks_w = _quant_heads(k_new)
        v_w, vs_w = _quant_heads(v_new)
    else:
        k_w, v_w = k_new, v_new
    ck, cv = cache["k"], cache["v"]
    Sc = ck.shape[1]
    bidx = jnp.arange(B)
    if window > 0 and Sc == window:  # ring buffer
        slot = pos % window
        idx = slot
        s = jnp.arange(window)[None, :]
        # slot s holds absolute position pos - ((pos - s) mod W); neg => unwritten
        kpos = pos[:, None] - jnp.mod(pos[:, None] - s, window)
    else:
        idx = pos
        kpos = jnp.broadcast_to(jnp.arange(Sc)[None, :], (B, Sc))
    ck = ck.at[bidx, idx].set(k_w[:, 0])
    cv = cv.at[bidx, idx].set(v_w[:, 0])
    out_cache = {"k": ck, "v": cv}
    if quant:
        ks = cache["ks"].at[bidx, idx].set(ks_w[:, 0])
        vs = cache["vs"].at[bidx, idx].set(vs_w[:, 0])
        out_cache["ks"], out_cache["vs"] = ks, vs
        ck = (ck.astype(jnp.float32) * ks).astype(k_new.dtype)
        cv = (cv.astype(jnp.float32) * vs).astype(v_new.dtype)
    bias = _causal_bias(pos[:, None], kpos, window)  # (B,1,1,1,Sc)

    o = _sdpa(q, ck, cv, bias)
    return _out_proj(p, o, layout, policy), out_cache
