"""TransformerLM: one composable decoder covering all 10 assigned archs.

Pure-functional: params are nested dict pytrees; `forward`/`prefill`/
`decode_step` are jit/pjit-compatible. Layers run python-unrolled (accurate
HLO cost/collective accounting — see DESIGN.md §Roofline) or under
lax.scan + remat for the memory-bounded full train_step artifact.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv as rwkv_mod
from repro.models.cache import kv_head_layout
from repro.models.layers import (
    RunPolicy,
    apply_norm,
    dense_init,
    mlp_apply,
    mlp_init,
    norm_init,
    sinusoidal_table,
)
from repro.models.layout import HeadLayout


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _layer_init(cfg: ArchConfig, kind: str, key, dtype, tp: int) -> Dict[str, Any]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": norm_init(cfg.norm, cfg.d_model, dtype),
                         "norm2": norm_init(cfg.norm, cfg.d_model, dtype)}
    if kind in ("attention", "local"):
        p["mixer"] = attn.attn_init(cfg, kv_head_layout(cfg, tp), k1, dtype)
    elif kind == "rglru":
        p["mixer"] = rglru_mod.rglru_init(cfg, k1, dtype)
    elif kind == "rwkv6":
        p["mixer"] = rwkv_mod.rwkv_att_init(cfg, k1, dtype)
    else:
        raise ValueError(kind)
    if kind == "rwkv6":
        p["ffn"] = rwkv_mod.rwkv_ffn_init(cfg, k2, dtype)
    elif cfg.is_moe:
        p["ffn"] = moe_mod.moe_init(cfg, k2, dtype, tp)
    else:
        p["ffn"] = mlp_init(cfg, k2, dtype)
    return p


def init_params(cfg: ArchConfig, key, *, dtype=jnp.float32, tp: int = 1) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.num_layers + 3)
    params: Dict[str, Any] = {
        "layers": [
            _layer_init(cfg, kind, keys[i], dtype, tp)
            for i, kind in enumerate(cfg.layer_kinds())
        ],
        "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    need_embed = cfg.input_kind == "tokens" or cfg.tie_embeddings
    if need_embed:
        params["embed"] = {
            "w": dense_init(keys[-1], (cfg.vocab_size, cfg.d_model), dtype,
                            in_axis_size=cfg.d_model)
        }
    if not cfg.tie_embeddings:
        params["head"] = {"w": dense_init(keys[-2], (cfg.d_model, cfg.vocab_size), dtype)}
    return params


def init_params_specs(cfg: ArchConfig, *, dtype=jnp.bfloat16, tp: int = 1):
    """ShapeDtypeStruct tree of params (no allocation) — dry-run input."""
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), dtype=dtype, tp=tp))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _block_apply(cfg, kind: str, p, x, policy: RunPolicy, layout: Optional[HeadLayout],
                 positions) -> Tuple[jax.Array, jax.Array]:
    """Residual block. Returns (x, moe_aux)."""
    h = apply_norm(cfg.norm, x, p["norm1"])
    if kind in ("attention", "local"):
        window = cfg.local_window if kind == "local" else 0
        mixed, _ = attn.attn_apply(cfg, p["mixer"], h, layout, policy,
                                   window=window, positions=positions,
                                   yarn=cfg.yarn_for(kind))
    elif kind == "rglru":
        mixed = rglru_mod.rglru_apply(cfg, p["mixer"], h, policy)
    elif kind == "rwkv6":
        mixed = rwkv_mod.rwkv_att_apply(cfg, p["mixer"], h, policy)
    else:
        raise ValueError(kind)
    x = x + mixed
    x = policy.c(x, "residual")
    h = apply_norm(cfg.norm, x, p["norm2"])
    aux = jnp.zeros((), jnp.float32)
    if kind == "rwkv6":
        y = rwkv_mod.rwkv_ffn_apply(cfg, p["ffn"], h)
    elif cfg.is_moe:
        y, aux = moe_mod.moe_apply(cfg, p["ffn"], h, policy, tp=policy_tp(policy))
    else:
        y = mlp_apply(cfg, p["ffn"], h, policy)
    x = x + y
    return policy.c(x, "residual"), aux


def policy_tp(policy: RunPolicy) -> int:
    return getattr(policy, "_tp", 1)


def set_policy_tp(policy: RunPolicy, tp: int) -> RunPolicy:
    policy._tp = tp  # stored out-of-band; moe padding depends on it
    return policy


# ---------------------------------------------------------------------------
# Stacked-layer layout (scan-friendly: one (L, ...) tree instead of L dicts).
# MaxText-style storage for scanned layers — the memory-artifact lowerings use
# it so scan-bwd accumulates into param-shaped buffers, not L separate ones.
# ---------------------------------------------------------------------------


def is_stacked(params) -> bool:
    return isinstance(params["layers"], dict)


def stack_params(params):
    """{'layers': [d0..dL-1]} -> {'layers': tree with leading L dim}."""
    if is_stacked(params):
        return params
    out = dict(params)
    out["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *params["layers"])
    return out


def layer_slice(layers, i: int):
    """Layer i's param dict from either layout."""
    if isinstance(layers, dict):
        return jax.tree.map(lambda a: a[i], layers)
    return layers[i]


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------


def embed_in(cfg, params, tokens, policy: RunPolicy, positions):
    if cfg.input_kind == "embeddings" and tokens.ndim == 3:
        x = tokens
    else:
        w = params["embed"]["w"]
        if policy.onehot_embed:
            oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=w.dtype)
            x = jnp.einsum("bsv,vd->bsd", oh, w)
        else:
            x = jnp.take(w, tokens, axis=0)
    if cfg.pos_emb == "sinusoidal":
        x = x + sinusoidal_table(positions, cfg.d_model).astype(x.dtype)
    return x


def logits_out(cfg, params, x, policy: RunPolicy):
    if cfg.tie_embeddings:
        w = params["embed"]["w"]  # (V,d)
        logits = jnp.einsum("bsd,vd->bsv", x, w, preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["head"]["w"],
                            preferred_element_type=jnp.float32)
    return policy.c(logits, "logits")


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def forward(cfg: ArchConfig, params, tokens, policy: RunPolicy,
            positions=None) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B,S) int32 or (B,S,d) embeddings. Returns (logits, moe_aux)."""
    S = tokens.shape[1]
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    layout = kv_head_layout(cfg, policy_tp(policy)) if cfg.mixer != "rwkv6" else None
    x = embed_in(cfg, params, tokens, policy, positions)
    x = policy.c(x, "residual")
    kinds = cfg.layer_kinds()
    aux_total = jnp.zeros((), jnp.float32)

    homogeneous = len(set(kinds)) == 1
    if policy.scan_layers and homogeneous:
        stacked = stack_params(params)["layers"]

        def body(carry, lp):
            h, aux = carry
            h, a = _block_apply(cfg, kinds[0], lp, h, policy, layout, positions)
            return (h, aux + a), None

        body_fn = jax.checkpoint(body) if policy.remat else body
        (x, aux_total), _ = jax.lax.scan(body_fn, (x, aux_total), stacked)
    else:
        for i, kind in enumerate(kinds):
            def blk(h, lp, _kind=kind):
                return _block_apply(cfg, _kind, lp, h, policy, layout, positions)

            if policy.remat:
                blk = jax.checkpoint(blk)
            x, a = blk(x, layer_slice(params["layers"], i))
            aux_total = aux_total + a

    x = apply_norm(cfg.norm, x, params["final_norm"])
    return logits_out(cfg, params, x, policy), aux_total


def loss_fn(cfg: ArchConfig, params, batch: Dict[str, Any], policy: RunPolicy):
    """Next-token cross-entropy (labels already shifted by the data pipeline)."""
    logits, aux = forward(cfg, params, batch["tokens"], policy)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = ce + 0.01 * aux / max(1, cfg.num_layers)
    return loss, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def prefill(cfg: ArchConfig, params, tokens, policy: RunPolicy
            ) -> Tuple[jax.Array, List[Dict[str, Any]]]:
    """Run the full prompt, return (last-position logits, decode cache).

    With policy.scan_layers (homogeneous archs) layers run under lax.scan and
    the cache comes back L-stacked — the memory-bounded lowering used by the
    dry-run's prefill_memory artifact.
    """
    B, S = tokens.shape[0], tokens.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    layout = kv_head_layout(cfg, policy_tp(policy)) if cfg.mixer != "rwkv6" else None
    kinds = cfg.layer_kinds()
    if policy.scan_layers and len(set(kinds)) == 1 and kinds[0] in ("attention", "rwkv6"):
        x = embed_in(cfg, params, tokens, policy, positions)
        stacked = stack_params(params)["layers"]

        def body(h, lp):
            h0 = apply_norm(cfg.norm, h, lp["norm1"])
            if kinds[0] == "attention":
                mixed, c = attn.attn_apply(cfg, lp["mixer"], h0, layout, policy,
                                           positions=positions,
                                           yarn=cfg.yarn_for(kinds[0]))
            else:
                mixed, ac = rwkv_mod.rwkv_att_apply(cfg, lp["mixer"], h0, policy,
                                                    return_cache=True)
                c = {"s": ac["s"], "xa": ac["x_prev"]}
            h = policy.c(h + mixed, "residual")
            h2 = apply_norm(cfg.norm, h, lp["norm2"])
            if kinds[0] == "rwkv6":
                y, xf = rwkv_mod.rwkv_ffn_apply(cfg, lp["ffn"], h2, return_cache=True)
                c["xf"] = xf
            elif cfg.is_moe:
                y, _ = moe_mod.moe_apply(cfg, lp["ffn"], h2, policy,
                                         tp=policy_tp(policy))
            else:
                y = mlp_apply(cfg, lp["ffn"], h2, policy)
            return policy.c(h + y, "residual"), c

        x, caches = jax.lax.scan(body, x, stacked)
        x = apply_norm(cfg.norm, x, params["final_norm"])
        return logits_out(cfg, params, x[:, -1:], policy), caches
    x = embed_in(cfg, params, tokens, policy, positions)
    caches: List[Dict[str, Any]] = []
    for i, kind in enumerate(cfg.layer_kinds()):
        p = layer_slice(params["layers"], i)
        h = apply_norm(cfg.norm, x, p["norm1"])
        if kind in ("attention", "local"):
            window = cfg.local_window if kind == "local" else 0
            mixed, kv = attn.attn_apply(cfg, p["mixer"], h, layout, policy,
                                        window=window, positions=positions,
                                        yarn=cfg.yarn_for(kind))
            if kind == "local" and S > cfg.local_window:
                W = cfg.local_window
                ring_k = jnp.roll(kv["k"][:, S - W:], S % W, axis=1)
                ring_v = jnp.roll(kv["v"][:, S - W:], S % W, axis=1)
                caches.append({"k": ring_k, "v": ring_v})
            else:
                caches.append(kv)
        elif kind == "rglru":
            mixed, c = rglru_mod.rglru_apply(cfg, p["mixer"], h, policy, return_cache=True)
            caches.append(c)
        elif kind == "rwkv6":
            mixed, c = rwkv_mod.rwkv_att_apply(cfg, p["mixer"], h, policy, return_cache=True)
            caches.append({"s": c["s"], "xa": c["x_prev"], "xf": None})
        x = x + mixed
        x = policy.c(x, "residual")
        h2 = apply_norm(cfg.norm, x, p["norm2"])
        if kind == "rwkv6":
            y, xf = rwkv_mod.rwkv_ffn_apply(cfg, p["ffn"], h2, return_cache=True)
            caches[-1]["xf"] = xf
        elif cfg.is_moe:
            y, _ = moe_mod.moe_apply(cfg, p["ffn"], h2, policy, tp=policy_tp(policy))
        else:
            y = mlp_apply(cfg, p["ffn"], h2, policy)
        x = policy.c(x + y, "residual")
    x = apply_norm(cfg.norm, x, params["final_norm"])
    logits = logits_out(cfg, params, x[:, -1:], policy)
    return logits, caches


def decode_step(cfg: ArchConfig, params, tokens, pos, cache, policy: RunPolicy
                ) -> Tuple[jax.Array, List[Dict[str, Any]]]:
    """One token: tokens (B,1) | (B,1,d); pos (B,) absolute positions.

    With policy.scan_layers + stacked params + stacked cache (leading L dim),
    layers run under lax.scan — the dry-run's decode_memory lowering (cache
    update buffers are reused across layers)."""
    layout = kv_head_layout(cfg, policy_tp(policy)) if cfg.mixer != "rwkv6" else None
    kinds = cfg.layer_kinds()
    if (policy.scan_layers and len(set(kinds)) == 1
            and kinds[0] in ("attention", "rwkv6") and is_stacked(params)
            and isinstance(cache, dict)):
        x = embed_in(cfg, params, tokens, policy, pos[:, None])

        def body(h, lp_c):
            lp, c = lp_c
            h0 = apply_norm(cfg.norm, h, lp["norm1"])
            if kinds[0] == "attention":
                mixed, nc = attn.attn_decode(cfg, lp["mixer"], h0, layout, policy,
                                             pos, c, yarn=cfg.yarn_for(kinds[0]))
            else:
                mixed, ac = rwkv_mod.rwkv_att_apply(cfg, lp["mixer"], h0, policy,
                                                    x_prev=c["xa"], s0=c["s"],
                                                    return_cache=True)
                nc = {"s": ac["s"], "xa": ac["x_prev"], "xf": c["xf"]}
            h = h + mixed
            h2 = apply_norm(cfg.norm, h, lp["norm2"])
            if kinds[0] == "rwkv6":
                y, xf = rwkv_mod.rwkv_ffn_apply(cfg, lp["ffn"], h2, x_prev=c["xf"],
                                                return_cache=True)
                nc["xf"] = xf
            elif cfg.is_moe:
                y, _ = moe_mod.moe_apply(cfg, lp["ffn"], h2, policy,
                                         tp=policy_tp(policy))
            else:
                y = mlp_apply(cfg, lp["ffn"], h2, policy)
            return h + y, nc

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        x = apply_norm(cfg.norm, x, params["final_norm"])
        return logits_out(cfg, params, x, policy), new_cache

    x = embed_in(cfg, params, tokens, policy, pos[:, None])
    new_cache: List[Dict[str, Any]] = []
    for i, kind in enumerate(kinds):
        p = layer_slice(params["layers"], i)
        c = cache[i]
        h = apply_norm(cfg.norm, x, p["norm1"])
        if kind in ("attention", "local"):
            window = cfg.local_window if kind == "local" else 0
            mixed, nc = attn.attn_decode(cfg, p["mixer"], h, layout, policy, pos, c,
                                         window=window, yarn=cfg.yarn_for(kind))
        elif kind == "rglru":
            mixed, nc = rglru_mod.rglru_decode(cfg, p["mixer"], h, policy, c)
        elif kind == "rwkv6":
            mixed, ac = rwkv_mod.rwkv_att_apply(cfg, p["mixer"], h, policy,
                                                x_prev=c["xa"], s0=c["s"],
                                                return_cache=True)
            nc = {"s": ac["s"], "xa": ac["x_prev"], "xf": c["xf"]}
        x = x + mixed
        h2 = apply_norm(cfg.norm, x, p["norm2"])
        if kind == "rwkv6":
            y, xf = rwkv_mod.rwkv_ffn_apply(cfg, p["ffn"], h2, x_prev=c["xf"],
                                            return_cache=True)
            nc["xf"] = xf
        elif cfg.is_moe:
            y, _ = moe_mod.moe_apply(cfg, p["ffn"], h2, policy, tp=policy_tp(policy))
        else:
            y = mlp_apply(cfg, p["ffn"], h2, policy)
        x = x + y
        new_cache.append(nc)
    x = apply_norm(cfg.norm, x, params["final_norm"])
    return logits_out(cfg, params, x, policy), new_cache


# ---------------------------------------------------------------------------
# Exactness hooks for the padded TP head layout (see models/layout.py)
# ---------------------------------------------------------------------------


def grad_mask(cfg: ArchConfig, params, tp: int):
    """0/1 tree: zero out grads of structurally-padded parameters.

    Masks broadcast from the right, so the same mask tree serves both the
    per-layer-list and the stacked (L, ...) layouts."""
    mask = jax.tree.map(lambda a: jnp.ones((), a.dtype), params)
    if cfg.mixer == "rwkv6":
        return mask
    lay = kv_head_layout(cfg, tp)
    qm = jnp.asarray(~lay.q_pad_mask(), jnp.float32)  # 1 = real head
    km = jnp.asarray(~lay.kv_pad_mask(), jnp.float32)
    stacked = is_stacked(params)
    entries = [mask["layers"]] if stacked else [
        mask["layers"][i] for i, kind in enumerate(cfg.layer_kinds())
        if kind in ("attention", "local")]
    for m_l in entries:
        m = m_l["mixer"]
        m["wq"] = qm[None, :, None]
        m["wo"] = qm[:, None, None]
        if lay.pad:
            m["wk"] = km[None, :, None]
            m["wv"] = km[None, :, None]
        if cfg.qkv_bias:
            m["bq"] = qm[:, None]
            if lay.pad:
                m["bk"] = km[:, None]
                m["bv"] = km[:, None]
    if cfg.is_moe and moe_mod.num_experts_eff(cfg, tp) != cfg.num_experts:
        em = (jnp.arange(moe_mod.num_experts_eff(cfg, tp)) < cfg.num_experts
              ).astype(jnp.float32)
        ffns = [mask["layers"]["ffn"]] if stacked else [
            mask["layers"][i]["ffn"] for i in range(cfg.num_layers)]
        for f in ffns:
            f["router"] = em[None, :]
            for kname in ("w_gate", "w_up", "w_down"):
                f[kname] = em[:, None, None]
    return mask


def sync_replica_grads(cfg: ArchConfig, grads, tp: int):
    """Sum KV-projection grads across replicas (keeps replicas identical)."""
    if cfg.mixer == "rwkv6":
        return grads
    lay = kv_head_layout(cfg, tp)
    if lay.rep == 1:
        return grads
    if is_stacked(grads):
        g = grads["layers"]["mixer"]
        g["wk"] = lay.reduce_kv_grad(g["wk"], 2)  # (L, d, Hkv, hd)
        g["wv"] = lay.reduce_kv_grad(g["wv"], 2)
        if cfg.qkv_bias:
            g["bk"] = lay.reduce_kv_grad(g["bk"], 1)
            g["bv"] = lay.reduce_kv_grad(g["bv"], 1)
        return grads
    for i, kind in enumerate(cfg.layer_kinds()):
        if kind not in ("attention", "local"):
            continue
        g = grads["layers"][i]["mixer"]
        g["wk"] = lay.reduce_kv_grad(g["wk"], 1)
        g["wv"] = lay.reduce_kv_grad(g["wv"], 1)
        if cfg.qkv_bias:
            g["bk"] = lay.reduce_kv_grad(g["bk"], 0)
            g["bv"] = lay.reduce_kv_grad(g["bv"], 0)
    return grads
