"""Token-choice top-k MoE: a dropless layer for serving, and capacity-bounded
dispatch (GShard-style dense, or sorted) for the training and dry-run paths.

Experts are padded to a multiple of the model axis (granite: 40 -> 48) with
-inf router logits on pads — exact, pads are never routed to. Expert weights
shard over the model axis (expert parallelism); the dispatch/combine einsums
lower to all-to-all-like collectives under GSPMD.

``moe_apply_dropless`` sorts the (token, expert) rows by expert and runs each
projection as one grouped matmul (``jax.lax.ragged_dot``): every token reaches
all of its top-k experts, whatever its batch-mates route, so a served
request's output does not depend on what it is batched with. On the TPU the
grouped matmuls are the ``ragged-dot`` operations of the device trace.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import RunPolicy, dense_init, zeros_init

NEG_INF = -1e30


def num_experts_eff(cfg, tp: int) -> int:
    return int(math.ceil(cfg.num_experts / tp) * tp)


def moe_init(cfg, key, dtype, tp: int) -> Dict[str, Any]:
    """Draw logical-size experts, then zero-pad to E_eff: the padded init is
    exactly the unpadded init (tp-equivalence, like the attention layout)."""
    d, f = cfg.d_model, cfg.d_ff
    E0 = cfg.num_experts
    E = num_experts_eff(cfg, tp)
    ks = jax.random.split(key, 4)
    pad = E - E0
    p = {
        "router": dense_init(ks[0], (d, E0), dtype, in_axis_size=d),
        "w_gate": dense_init(ks[1], (E0, d, f), dtype, in_axis_size=d),
        "w_up": dense_init(ks[2], (E0, d, f), dtype, in_axis_size=d),
        "w_down": dense_init(ks[3], (E0, f, d), dtype, in_axis_size=f),
    }
    if pad:
        p["router"] = jnp.pad(p["router"], ((0, 0), (0, pad)))
        for k in ("w_gate", "w_up", "w_down"):
            p[k] = jnp.pad(p[k], ((0, pad), (0, 0), (0, 0)))
    return p


def _route(cfg, router, xt):
    """Softmax over the real experts in float32, top-k, renormalised:
    (gates (T, K) f32, expert ids (T, K))."""
    logits = (xt @ router.astype(jnp.float32)).astype(jnp.float32)
    if router.shape[-1] != cfg.num_experts:
        pad = jnp.arange(router.shape[-1]) >= cfg.num_experts
        logits = jnp.where(pad[None, :], NEG_INF, logits)
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    return gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9), idx


def moe_apply_dropless(cfg, p, x) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (y, experts that received at least one row, int32).

    The T*K (token, expert) rows are sorted by expert, and each projection
    is one grouped matmul over the sorted rows; no row is dropped. Each
    token's K outputs are combined with its gates in float32."""
    B, S, d = x.shape
    K = cfg.top_k
    xt = x.reshape(B * S, d)
    gates, idx = _route(cfg, p["router"], xt)
    flat = idx.reshape(-1)  # row r is token r // K, slot r % K
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=p["router"].shape[-1]).astype(jnp.int32)
    rows = xt[order // K]

    def grouped(a, w):
        return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=jnp.float32)

    h = (jax.nn.silu(grouped(rows, p["w_gate"]))
         * grouped(rows, p["w_up"])).astype(x.dtype)
    ye = grouped(h, p["w_down"])  # (T*K, d) f32, sorted by expert
    back = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
    y = (gates[:, :, None] * ye[back].reshape(B * S, K, d)).sum(axis=1)
    return y.astype(x.dtype).reshape(B, S, d), jnp.sum(sizes > 0, dtype=jnp.int32)


def moe_apply(cfg, p, x, policy: RunPolicy, tp: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Dispatcher: GShard-style dense dispatch (baseline) or sort-based
    scatter dispatch (beyond-paper §Perf: removes the O(T*E*C*d) dispatch
    einsums — the dominant waste in MoE prefill)."""
    if getattr(policy, "moe_impl", "dense") == "sorted":
        return moe_apply_sorted(cfg, p, x, policy, tp=tp)
    return moe_apply_dense(cfg, p, x, policy, tp=tp)


def moe_apply_sorted(cfg, p, x, policy: RunPolicy, tp: int = 1
                     ) -> Tuple[jax.Array, jax.Array]:
    """Sort/scatter dispatch (megablocks-style, capacity-padded).

    FLOPs = expert FFN only (~2*3*T*k*d*f); dispatch/combine are scatters and
    gathers, not matmuls. Same drop semantics as the dense path (per-expert
    capacity, slot-0-first priority). Distributed: shard_map EP — each model
    rank routes its data-shard's tokens to its local experts and the partial
    outputs psum over 'model' (one bf16 all-reduce, like any TP layer)."""
    if policy.mesh is not None and tp > 1:
        return _moe_sorted_ep(cfg, p, x, policy, tp)
    B, S, d = x.shape
    E, K = num_experts_eff(cfg, tp), cfg.top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt @ p["router"].astype(jnp.float32)).astype(jnp.float32)
    if E != cfg.num_experts:
        padm = jnp.arange(E) >= cfg.num_experts
        logits = jnp.where(padm[None, :], NEG_INF, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = int(max(4, math.ceil(T * K / cfg.num_experts * policy.moe_capacity_factor)))
    cap = min(cap, T)

    # slot-major flattening: ALL slot-0 routings take queue positions before
    # any slot-1 (bit-identical drop priority to the dense path)
    expert_flat = idx.T.reshape(-1)  # (K*T,)
    token_flat = jnp.tile(jnp.arange(T), K)
    gate_flat = gate_vals.T.reshape(-1)
    order = jnp.argsort(expert_flat, stable=True)
    e_sorted = expert_flat[order]
    t_sorted = token_flat[order]
    g_sorted = gate_flat[order]
    counts = jnp.bincount(expert_flat, length=E)
    starts = jnp.cumsum(counts) - counts  # exclusive
    pos_in_e = jnp.arange(T * K) - starts[e_sorted]
    keep = pos_in_e < cap
    slot = jnp.where(keep, e_sorted * cap + pos_in_e, E * cap)  # E*cap = trash

    xe = jnp.zeros((E * cap + 1, d), x.dtype).at[slot].set(xt[t_sorted])
    xe = policy.c(xe[:-1].reshape(E, cap, d), "moe_experts")
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"],
                               preferred_element_type=jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"], preferred_element_type=jnp.float32)
    h = (g * u).astype(x.dtype)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"],
                    preferred_element_type=jnp.float32).astype(x.dtype)
    ye = policy.c(ye, "moe_experts").reshape(E * cap, d)
    contrib = jnp.where(keep, g_sorted, 0.0)[:, None].astype(x.dtype) * ye[
        jnp.minimum(slot, E * cap - 1)]
    y = jnp.zeros((T, d), x.dtype).at[t_sorted].add(contrib)

    me = probs[:, : cfg.num_experts].mean(axis=0)
    ce = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(axis=1)[
        :, : cfg.num_experts].mean(axis=0)
    aux = cfg.num_experts * jnp.sum(me * ce)
    return y.reshape(B, S, d), aux


def _moe_sorted_ep(cfg, p, x, policy: RunPolicy, tp: int) -> Tuple[jax.Array, jax.Array]:
    """shard_map expert parallelism for the sorted dispatch (see above)."""
    from jax.sharding import PartitionSpec as P

    mesh = policy.mesh
    dp = tuple(a for a in mesh.axis_names if a != "model")
    dp_entry = dp if len(dp) > 1 else dp[0]
    B, S, d = x.shape
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if B % dp_size != 0:
        dp_entry = None
        dp_size = 1
    E, K = num_experts_eff(cfg, tp), cfg.top_k
    E_loc = E // tp
    T_loc = (B // dp_size) * S
    cap = int(max(4, math.ceil(
        T_loc * K / cfg.num_experts * policy.moe_capacity_factor)))
    cap = min(cap, T_loc)

    def f(x_loc, router, wg, wu, wd):
        Bl, Sl, _ = x_loc.shape
        T = Bl * Sl
        xt = x_loc.reshape(T, d)
        logits = (xt @ router.astype(jnp.float32)).astype(jnp.float32)
        if E != cfg.num_experts:
            padm = jnp.arange(E) >= cfg.num_experts
            logits = jnp.where(padm[None, :], NEG_INF, logits)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        rank = jax.lax.axis_index("model")
        e_lo = rank * E_loc
        expert_flat = idx.T.reshape(-1)
        token_flat = jnp.tile(jnp.arange(T), K)
        gate_flat = gate_vals.T.reshape(-1)
        order = jnp.argsort(expert_flat, stable=True)
        e_sorted = expert_flat[order]
        t_sorted = token_flat[order]
        g_sorted = gate_flat[order]
        counts = jnp.bincount(expert_flat, length=E)
        starts = jnp.cumsum(counts) - counts
        pos_in_e = jnp.arange(T * K) - starts[e_sorted]
        local = (e_sorted >= e_lo) & (e_sorted < e_lo + E_loc)
        keep = (pos_in_e < cap) & local
        slot = jnp.where(keep, (e_sorted - e_lo) * cap + pos_in_e, E_loc * cap)

        xe = jnp.zeros((E_loc * cap + 1, d), x_loc.dtype).at[slot].set(xt[t_sorted])
        xe = xe[:-1].reshape(E_loc, cap, d)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg,
                                   preferred_element_type=jnp.float32))
        u = jnp.einsum("ecd,edf->ecf", xe, wu, preferred_element_type=jnp.float32)
        h = (g * u).astype(x_loc.dtype)
        ye = jnp.einsum("ecf,efd->ecd", h, wd,
                        preferred_element_type=jnp.float32).astype(x_loc.dtype)
        ye = ye.reshape(E_loc * cap, d)
        contrib = jnp.where(keep, g_sorted, 0.0)[:, None].astype(x_loc.dtype) * ye[
            jnp.minimum(slot, E_loc * cap - 1)]
        y = jnp.zeros((T, d), x_loc.dtype).at[t_sorted].add(contrib)
        y = jax.lax.psum(y, "model")  # sum expert-shard contributions

        me = probs[:, : cfg.num_experts].mean(axis=0)
        ce = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(axis=1)[
            :, : cfg.num_experts].mean(axis=0)
        aux = cfg.num_experts * jnp.sum(me * ce)
        return y.reshape(Bl, Sl, d), aux[None]

    y, aux = jax.shard_map(
        f, mesh=mesh,
        in_specs=(P(dp_entry, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(dp_entry, None, None), P(dp_entry)),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return y, aux.mean()


def moe_apply_dense(cfg, p, x, policy: RunPolicy, tp: int = 1) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (y, aux_loss). Capacity-dropped tokens pass through (residual)."""
    B, S, d = x.shape
    E, K = num_experts_eff(cfg, tp), cfg.top_k
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt @ p["router"].astype(jnp.float32)).astype(jnp.float32)
    if E != cfg.num_experts:
        pad = jnp.arange(E) >= cfg.num_experts
        logits = jnp.where(pad[None, :], NEG_INF, logits)
    probs = jax.nn.softmax(logits, axis=-1)  # (T,E)
    gate_vals, idx = jax.lax.top_k(probs, K)  # (T,K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    cap = int(max(4, math.ceil(T * K / cfg.num_experts * policy.moe_capacity_factor)))
    cap = min(cap, T)

    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)  # (T,K,E)
    # position of each (t,k) routing decision in its expert queue; slot 0 first.
    # top-k indices are distinct, so per (t,e) at most one slot fires and the
    # per-slot quantities can be summed into single (T,E) maps before building
    # the ONE (T,E,cap) combine tensor (keeps transients to a single buffer).
    pos_te = jnp.zeros((T, E), jnp.float32)
    gate_te = jnp.zeros((T, E), jnp.float32)
    hit_te = jnp.zeros((T, E), jnp.float32)
    prior = jnp.zeros((E,), jnp.float32)
    for s in range(K):
        m = onehot[:, s, :]
        pos_s = jnp.cumsum(m, axis=0) - m + prior[None, :]
        prior = prior + m.sum(axis=0)
        pos_te = pos_te + pos_s * m
        gate_te = gate_te + gate_vals[:, s, None] * m
        hit_te = hit_te + m
    within = hit_te * (pos_te < cap).astype(jnp.float32)
    slot = jax.nn.one_hot(jnp.minimum(pos_te, cap - 1).astype(jnp.int32), cap,
                          dtype=jnp.float32)  # (T,E,cap)
    combine = (gate_te * within)[:, :, None] * slot
    dispatch = (within[:, :, None] * slot).astype(x.dtype)

    xe = jnp.einsum("tec,td->ecd", dispatch, xt, preferred_element_type=jnp.float32).astype(x.dtype)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["w_gate"], preferred_element_type=jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", xe, p["w_up"], preferred_element_type=jnp.float32)
    h = (g * u).astype(x.dtype)
    ye = jnp.einsum("ecf,efd->ecd", h, p["w_down"], preferred_element_type=jnp.float32).astype(x.dtype)
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ye,
                   preferred_element_type=jnp.float32).astype(x.dtype)

    # Switch-style load-balance aux loss over the *real* experts
    me = probs[:, : cfg.num_experts].mean(axis=0)
    ce = onehot.sum(axis=1)[:, : cfg.num_experts].mean(axis=0)
    aux = cfg.num_experts * jnp.sum(me * ce)
    return y.reshape(B, S, d), aux
