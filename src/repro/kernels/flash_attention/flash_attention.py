"""Pallas TPU flash-attention forward kernel (GQA, causal, sliding window).

Tiling: grid (B, H, nQ, nK) with the KV dimension innermost (sequential on
TPU); online-softmax state (m, l, acc) lives in VMEM scratch and survives
across KV blocks. Fully-masked KV blocks are skipped via pl.when on the
block indices, so causal FLOPs track S^2/2 and window FLOPs track S*W.

Block shapes: q/o (1,1,BQ,D), k/v (1,1,BK,D) — MXU-aligned for D in
{64,128,256} and BQ/BK multiples of 128 (VMEM footprint ~ BQ*D + 2*BK*D +
BQ*BK floats).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, resolve_interpret, tpu_compiler_params


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, block_q: int, block_k: int, n_k: int,
            causal: bool, window: int, seq_k: int):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # kv block

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo = i * block_q
    k_lo = j * block_k
    # causal / window block-level liveness (dynamic on program ids)
    live = jnp.asarray(True)
    if causal:
        live &= k_lo <= q_lo + block_q - 1
    if window > 0:
        live &= k_lo + block_k - 1 > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (BQ, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        ok = kpos < seq_k
        if causal:
            ok &= kpos <= qpos
        if window > 0:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (BQ,1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(l_ref[:, :1] * alpha + p.sum(1, keepdims=True),
                                      l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    # last block that can touch this q row block
    if causal:
        j_last = jnp.minimum((q_lo + block_q - 1) // block_k, n_k - 1)
    else:
        j_last = n_k - 1

    @pl.when(j == j_last)
    def _write():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool | None = None):
    """q: (B,H,Sq,D); k,v: (B,Hkv,Sk,D). Returns (B,H,Sq,D)."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    group = H // Hkv
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    n_q, n_k = Sq // block_q, Sk // block_k
    grid = (B, H, n_q, n_k)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(D), block_q=block_q, block_k=block_k,
        n_k=n_k, causal=causal, window=window, seq_k=Sk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # m
            pltpu.VMEM((block_q, 128), jnp.float32),  # l
            pltpu.VMEM((block_q, D), jnp.float32),  # acc
        ],
        compiler_params=tpu_compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
