"""A sparse-expert decoder with sliding-window and full attention layers, as
the benchmark runs it (Mellum2's block): the program's config object, the
weights from the seed, and a plain float32 reference.

The configuration file (``bench/configs/mellum2-*.json``) keeps the
published ``config.json`` keys: ``layer_types`` (``sliding_attention`` /
``full_attention``) for the first ``num_hidden_layers`` layers,
``rope_parameters`` per layer type, ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``norm_topk_prob``.
``arch_config`` maps them onto the program's ``ArchConfig``; ``make_params``
builds the weights in the tree the program serves (``init_params``'s layout
at tp=1) on the device, in one jitted call. The reference (``ref_pass``,
``judge``) is written from the published description and imports nothing
of the program:

- sliding layers attend keys ``k > q - sliding_window``, full layers every
  key up to the query;
- RoPE (rotate-half) with ``rope_theta`` in both kinds; full layers scale
  it by YaRN as transformers' ``_compute_yarn_parameters`` defines it
  (the correction range from ``beta_fast`` and ``beta_slow`` over
  ``original_max_position_embeddings``, a linear ramp between extrapolated
  and interpolated frequencies, cos and sin times ``attention_factor``);
- the router's softmax over all experts in float32, the top
  ``num_experts_per_tok`` renormalised, and every expert computed for
  every token with the gates of the unchosen ones zero: no capacity, and
  nothing of the program's routing is copied.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops_moe import layer_types
from bench.model import _identity, _is_spec, fp8_cast, rel_err, seed_key

KINDS = {"sliding_attention": "local", "full_attention": "attention"}


def arch_config(cfg: Dict[str, Any]):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig, Yarn

    rope = cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if (sliding["rope_type"], full["rope_type"]) != ("default", "yarn") or \
            sliding["rope_theta"] != full["rope_theta"]:
        raise ValueError("the program runs plain RoPE on sliding layers and "
                         "YaRN on full ones, with one theta")
    if cfg["hidden_act"] != "silu" or not cfg["norm_topk_prob"]:
        raise ValueError("the program's experts are SwiGLU with "
                         "renormalised top-k gates")
    return ArchConfig(
        name=cfg["name"], family="moe", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        mixer="attention", mlp_act="swiglu", norm="rmsnorm",
        qkv_bias=cfg["attention_bias"], rope_theta=float(full["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        layer_pattern=tuple(KINDS[t] for t in layer_types(cfg)),
        local_window=cfg["sliding_window"],
        global_yarn=Yarn(
            factor=float(full["factor"]),
            original_max_position_embeddings=int(
                full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])))


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def leaf_specs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Tree of (shape, scale, offset) per leaf: a leaf is
    offset + scale * N(0, 1). Matrices are scaled by 1/sqrt(fan_in), so
    router logits are about unit-variance and spread tokens over the
    experts; norm scales sit near 1."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    f, E = cfg["moe_intermediate_size"], cfg["num_experts"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])

    def mat(shape, fan_in):
        return (tuple(shape), 1.0 / math.sqrt(fan_in), 0.0)

    def norm():
        return {"scale": ((d,), 0.1, 1.0)}

    def layer():
        mixer = {"wq": mat((d, hq, hd), d), "wk": mat((d, hk, hd), d),
                 "wv": mat((d, hk, hd), d), "wo": mat((hq, hd, d), hq * hd)}
        ffn = {"router": mat((d, E), d), "w_gate": mat((E, d, f), d),
               "w_up": mat((E, d, f), d), "w_down": mat((E, f, d), f)}
        return {"norm1": norm(), "norm2": norm(), "mixer": mixer, "ffn": ffn}

    tree = {"layers": [layer() for _ in range(cfg["num_hidden_layers"])],
            "final_norm": norm(), "embed": {"w": mat((V, d), d)}}
    if not cfg["tie_word_embeddings"]:
        tree["head"] = {"w": mat((d, V), d)}
    return tree


def make_params(cfg: Dict[str, Any], seed: int):
    """All weights, in the served dtype, from one jitted call on the device."""
    flat, tdef = jax.tree.flatten(leaf_specs(cfg), is_leaf=_is_spec)
    dtype = jnp.dtype(cfg["dtype"])

    @jax.jit
    def gen(key):
        out = []
        for i, (shape, scale, offset) in enumerate(flat):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, dtype)
            out.append((offset + scale * z).astype(dtype))
        return tdef.unflatten(out)

    return gen(seed_key(seed))


def param_count(cfg: Dict[str, Any]) -> int:
    flat = jax.tree.leaves(leaf_specs(cfg), is_leaf=_is_spec)
    return int(sum(np.prod(s) for s, _, _ in flat))


# ---------------------------------------------------------------------------
# Plain float32 reference
# ---------------------------------------------------------------------------


def yarn_frequencies(rope: Dict[str, Any], head_dim: int,
                     max_position_embeddings: int) -> Tuple[np.ndarray, float]:
    """transformers' ``_compute_yarn_parameters``: (inverse frequencies,
    attention factor)."""
    base, dim = float(rope["rope_theta"]), head_dim
    factor = float(rope["factor"])
    original = rope.get("original_max_position_embeddings")
    if original:  # as transformers: the factor is the context ratio then
        factor = max_position_embeddings / original
    else:
        original = max_position_embeddings
    attention_factor = rope.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    beta_fast = rope.get("beta_fast") or 32
    beta_slow = rope.get("beta_slow") or 1

    def correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrapolation = 1.0 / pos_freqs
    interpolation = 1.0 / (factor * pos_freqs)
    extrapolation_factor = 1 - ramp
    inv = (interpolation * (1 - extrapolation_factor)
           + extrapolation * extrapolation_factor)
    return inv.astype(np.float32), float(attention_factor)


def _rope(x, positions, inv_freq, scale):
    """Rotate-half RoPE. x (S, H, D), positions (S,)."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _norm(cfg, x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + cfg["rms_norm_eps"]) * scale


def _rope_of(cfg, layer_type):
    rope = cfg["rope_parameters"][layer_type]
    hd = cfg["head_dim"]
    if rope["rope_type"] == "yarn":
        return yarn_frequencies(rope, hd, cfg["max_position_embeddings"])
    inv = 1.0 / float(rope["rope_theta"]) ** (
        np.arange(0, hd, 2, dtype=np.float32) / hd)
    return inv.astype(np.float32), 1.0


def _experts(cfg, cast, p, h):
    """Sparse MLP of one sequence h (S, d): the gate-weighted sum of every
    expert's SwiGLU output, each gate zero but for the renormalised top-k;
    and each position's k-th and (k+1)-th router scores."""
    K, E = cfg["num_experts_per_tok"], cfg["num_experts"]
    logits = h @ cast(p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, K + 1)
    gates = top[:, :K] / top[:, :K].sum(-1, keepdims=True)
    dense = jnp.zeros((h.shape[0], E), jnp.float32).at[
        jnp.arange(h.shape[0])[:, None], idx[:, :K]].set(gates)

    def one(y, e):
        wg, wu, wd, g = e
        wg, wu, wd = (cast(w.astype(jnp.float32)) for w in (wg, wu, wd))
        a = cast(jax.nn.silu(h @ wg) * (h @ wu))
        return y + g[:, None] * (a @ wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (p["w_gate"], p["w_up"], p["w_down"], dense.T))
    return y, top[:, K - 1], top[:, K]


def _layer(cfg, layer_type, q_block, cast, p, x):
    """One decoder layer over one sequence x (S, d), causal (windowed in
    sliding layers): the layer's output, its K (after RoPE) and V as a KV
    cache holds them, and the router's k-th and (k+1)-th scores per
    position. ``cast`` rounds every matmul input (identity for the float32
    reference); norms stay in float32."""
    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    a, n1, n2 = f32(p["mixer"]), f32(p["norm1"]), f32(p["norm2"])
    S = x.shape[0]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    window = cfg["sliding_window"] if layer_type == "sliding_attention" else 0
    inv_freq, scale = _rope_of(cfg, layer_type)
    pos = jnp.arange(S)
    h = cast(_norm(cfg, x, n1["scale"]))
    q = jnp.einsum("sd,dhe->she", h, cast(a["wq"]))
    k = jnp.einsum("sd,dhe->she", h, cast(a["wk"]))
    v = jnp.einsum("sd,dhe->she", h, cast(a["wv"]))
    q = _rope(q, pos, inv_freq, scale).reshape(S, hk, hq // hk, hd)
    k = _rope(k, pos, inv_freq, scale)
    kc, vc = cast(k), cast(v)
    outs = []
    for s0 in range(0, S, q_block):  # a block of queries at a time
        qb = cast(q[s0:s0 + q_block])
        sc = jnp.einsum("qngd,knd->ngqk", qb, kc) / math.sqrt(hd)
        qpos = (s0 + jnp.arange(qb.shape[0]))[:, None]
        ok = pos[None, :] <= qpos
        if window:
            ok &= pos[None, :] > qpos - window
        pr = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("ngqk,knd->qngd", cast(pr), vc))
    o = cast(jnp.concatenate(outs).reshape(S, hq, hd))
    x = x + jnp.einsum("she,hed->sd", o, cast(a["wo"]))
    h2 = cast(_norm(cfg, x, n2["scale"]))
    y, kth, next_ = _experts(cfg, cast, p["ffn"], h2)
    return x + y, k, v, kth, next_


@functools.lru_cache(maxsize=None)
def _jitted(cfg_json: str, control: bool):
    cfg = json.loads(cfg_json)
    cast = fp8_cast if control else _identity

    @jax.jit
    def embed(w, toks):
        return cast(w[toks].astype(jnp.float32))

    @functools.partial(jax.jit, static_argnums=0)
    def layer(layer_type, p, x):
        with jax.default_matmul_precision("highest"):
            return _layer(cfg, layer_type, 512, cast, p, x)

    @jax.jit
    def logits_of(params, x):
        with jax.default_matmul_precision("highest"):
            h = _norm(cfg, x, params["final_norm"]["scale"].astype(jnp.float32))
            w = (params["embed"]["w"].T if cfg["tie_word_embeddings"]
                 else params["head"]["w"]).astype(jnp.float32)
            return cast(h) @ cast(w)

    @jax.jit
    def head(params, x, ids):
        """Per position: the best logit, the second best, the logit of
        ``ids``, and the argmax."""
        logits = logits_of(params, x)
        top2 = jax.lax.top_k(logits, 2)[0]
        picked = jnp.take_along_axis(logits, ids[:, None], -1)[:, 0]
        return top2[:, 0], top2[:, 1], picked, jnp.argmax(logits, -1)

    return embed, layer, head, logits_of


def _key(cfg) -> str:
    """The configuration's numbers and groups, as the cache key of the
    jitted reference."""
    return json.dumps({k: v for k, v in cfg.items()
                       if isinstance(v, (int, float, str, bool, dict, list))},
                      sort_keys=True)


def ref_pass(cfg: Dict[str, Any], params, tokens: np.ndarray, *,
             control: bool = False, keep_kv: bool = False):
    """The last layer's output for one sequence, computed layer by layer in
    float32 (``control``: every matmul input rounded to float8), with
    ``keep_kv`` each layer's (K, V) at the real positions as numpy arrays,
    and per layer the router's k-th and (k+1)-th scores at the real
    positions. The sequence is padded at its end to the engine's
    ``max_len``, which causal attention leaves without effect on the real
    positions: one shape a layer type, compiled once (a layer program at
    4,096 positions takes 12-17 s to compile for a v5e)."""
    embed, layer, _, _ = _jitted(_key(cfg), control)
    n = len(tokens)
    toks = np.zeros(max(n, cfg["engine"]["max_len"]), np.int32)
    toks[:n] = tokens
    x = embed(params["embed"]["w"], toks)
    kv, scores = [], []
    for t, p in zip(layer_types(cfg), params["layers"]):
        x, k, v, kth, next_ = layer(t, p, x)
        if keep_kv:
            kv.append((np.asarray(k[:n]), np.asarray(v[:n])))
        scores.append((np.asarray(kth[:n]), np.asarray(next_[:n])))
    return x, kv, scores


def _head(cfg, params, x, ids: np.ndarray, control: bool = False):
    head = _jitted(_key(cfg), control)[2]
    return [np.asarray(a) for a in head(params, x, ids)]


def ref_logits(cfg, params, tokens: np.ndarray) -> np.ndarray:
    """The reference's logits at every position of ``tokens`` (S, V)."""
    x, _, _ = ref_pass(cfg, params, tokens)
    return np.asarray(_jitted(_key(cfg), False)[3](params, x))[:len(tokens)]


def near_ties(scores) -> np.ndarray:
    """Per layer, the positions whose k-th and (k+1)-th router scores lie
    within one bf16 rounding step of each other (2**-8 of the k-th): there
    the program, which routes bf16 activations, may choose the other
    expert."""
    return np.array([int(np.sum(a - b <= a * 2.0 ** -8)) for a, b in scores])


def judge(cfg, params, seq: np.ndarray, served: List[int], *,
          kv=None, control: bool = False) -> Dict[str, np.ndarray]:
    """Reference readings of one request, as ``bench.model.judge`` reads
    them (``gap``, ``margin``, ``kv_err``), plus ``ties``: per layer the
    positions of ``seq`` whose routing is a near tie (``near_ties``)."""
    n, m = len(seq), len(served)
    # the served positions, padded to whole 256s by repeating the last: the
    # head runs on these rows only (a whole sequence's logits would take
    # 1.6e9 B at 4,096 positions and a 98,304-token vocabulary)
    rows = np.full(-(-m // 256) * 256, n - 1)
    rows[:m] = np.arange(n - m, n)
    want_kv = kv is not None
    x, ref_kv, scores = ref_pass(cfg, params, seq, keep_kv=want_kv)
    ids = np.zeros(len(rows), np.int32)
    ids[:m] = served
    if control:
        xc, kv, _ = ref_pass(cfg, params, seq, control=True, keep_kv=want_kv)
        ids[:m] = _head(cfg, params, xc[rows], ids, control=True)[3][:m]
    best, second, picked, _ = _head(cfg, params, x[rows], ids)
    out = {"gap": (best - picked)[:m], "margin": (best - second)[:m],
           "ties": near_ties(scores)}
    if want_kv:
        out["kv_err"] = np.array([max(rel_err(k, rk), rel_err(v, rv))
                                  for (k, v), (rk, rv) in zip(kv, ref_kv)])
    return out
