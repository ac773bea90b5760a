"""Pallas TPU paged decode attention.

The KV cache lives in a head-major page pool (P, Hkv, PS, D); each sequence
owns a row of the page table — the serving-side materialization of the
paper's system page table. The page table and sequence lengths ride in
scalar-prefetch (SMEM): the k/v BlockSpec index_maps dereference the table
so each grid step DMAs exactly one page of one kv head from HBM into VMEM.
Head-major keeps that block's last two dims (PS, D) whole, as Mosaic's
(8, 128) tiling rule requires; a token-major (1, PS, 1, D) block is refused
by the compiler. Pages past a sequence's length are skipped (no DMA-compute
on dead pages).

A static ``window`` > 0 is sliding-window attention: the new token at
position ``length - 1`` attends keys ``kpos > length - 1 - window``. Pages
wholly before the window are skipped like pages past the length: no compute,
and their index map repeats the window's first page, so no DMA either.
``window=0`` traces the unwindowed kernel unchanged.

Grid: (B, Hkv, NP) — page dim innermost, online softmax in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, resolve_interpret, tpu_compiler_params


def _first_page(length, window: int, page_size: int):
    """The first page holding a key inside the window of a sequence."""
    return jnp.maximum(length - window, 0) // page_size


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, page_size: int, group: int, window: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    live = j * page_size < length
    if window:
        live &= j >= _first_page(length, window, page_size)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (group, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (PS, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(q.shape[-1]))
        kpos = j * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = kpos < length
        if window:
            ok &= kpos >= length - window
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(l_ref[:, :1] * alpha + p.sum(1, keepdims=True),
                                      l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    j_final = jnp.maximum((length - 1) // page_size, 0)

    @pl.when(j == j_final)
    def _write():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_fwd(q, k_pool, v_pool, page_table, lengths, *,
                        window: int = 0, interpret: bool | None = None):
    """q: (B,H,D); pools: (P,Hkv,PS,D); page_table: (B,NP); lengths: (B,);
    ``window``: keys attended from the end (0: all)."""
    B, H, D = q.shape
    P, Hkv, PS, _ = k_pool.shape
    NP = page_table.shape[1]
    assert H % Hkv == 0
    group = H // Hkv
    grid = (B, Hkv, NP)
    kernel = functools.partial(_kernel, page_size=PS, group=group,
                               window=window)

    def kv_index(b, h, j, pt, ln):
        if window:
            j = jnp.maximum(j, _first_page(ln[b], window, PS))
        return pt[b, j], h, 0, 0

    # q viewed as (B, Hkv, group, D) so each grid step reads one kv-group
    q4 = q.reshape(B, Hkv, group, D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, D), lambda b, h, j, pt, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, PS, D), kv_index),
            pl.BlockSpec((1, 1, PS, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, group, D), lambda b, h, j, pt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, 128), jnp.float32),
            pltpu.VMEM((group, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        compiler_params=tpu_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(page_table, lengths, q4, k_pool, v_pool)
    return out.reshape(B, H, D)
