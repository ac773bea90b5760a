"""A dense decoder configuration as the benchmark runs it: the program's
config object, the weights from the seed, and a plain float32 reference.

The configuration files (``bench/configs/*.json``) use the published
``config.json`` key names. ``arch_config`` maps them onto the program's
``ArchConfig``; ``make_params`` builds the weights in the tree the program
serves (``repro.models.init_params``'s layout at tp=1), on the device, in
one jitted call; ``ref_logits`` is the reference forward, written from the
published description and importing nothing of the program.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_ACT = {"silu": "swiglu", "gelu_pytorch_tanh": "gelu"}


def arch_config(cfg: Dict[str, Any]):
    """The program's ArchConfig for a configuration file."""
    from repro.configs.base import ArchConfig

    return ArchConfig(
        name=cfg["name"], family="dense", source=cfg["source"],
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        mixer="attention", mlp_act=_ACT[cfg["hidden_act"]],
        norm=cfg["norm_type"], qkv_bias=cfg["qkv_bias"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"])


def seed_key(seed: int):
    """A PRNG key for any whole number, also past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def leaf_specs(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Tree of (shape, scale, offset) per leaf: a leaf is
    offset + scale * N(0, 1). Matrices are scaled by 1/sqrt(fan_in); norm
    scales sit near 1 and biases near 0, so both paths carry signal."""
    d, f, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])

    def mat(shape, fan_in):
        return (tuple(shape), 1.0 / math.sqrt(fan_in), 0.0)

    def norm():
        out = {"scale": ((d,), 0.1, 1.0)}
        if cfg["norm_type"] == "layernorm":
            out["bias"] = ((d,), 0.1, 0.0)
        return out

    def layer():
        mixer = {"wq": mat((d, hq, hd), d), "wk": mat((d, hk, hd), d),
                 "wv": mat((d, hk, hd), d), "wo": mat((hq, hd, d), hq * hd)}
        if cfg["qkv_bias"]:
            mixer.update(bq=((hq, hd), 0.1, 0.0), bk=((hk, hd), 0.1, 0.0),
                         bv=((hk, hd), 0.1, 0.0))
        if cfg["hidden_act"] == "silu":
            ffn = {"w_gate": mat((d, f), d), "w_up": mat((d, f), d),
                   "w_down": mat((f, d), f)}
        else:
            ffn = {"w_up": mat((d, f), d), "b_up": ((f,), 0.1, 0.0),
                   "w_down": mat((f, d), f), "b_down": ((d,), 0.1, 0.0)}
        return {"norm1": norm(), "norm2": norm(), "mixer": mixer, "ffn": ffn}

    tree = {"layers": [layer() for _ in range(cfg["num_hidden_layers"])],
            "final_norm": norm(), "embed": {"w": mat((V, d), d)}}
    if not cfg["tie_word_embeddings"]:
        tree["head"] = {"w": mat((d, V), d)}
    return tree


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def make_params(cfg: Dict[str, Any], seed: int):
    """All weights, in the served dtype, from one jitted call on the device."""
    specs = leaf_specs(cfg)
    flat, tdef = jax.tree.flatten(specs, is_leaf=_is_spec)
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


def _norm(cfg, x, p):
    if cfg["norm_type"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                              + cfg["rms_norm_eps"])
        return y * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + cfg["norm_epsilon"]) * p["scale"] + p["bias"]


def _rope(x, positions, theta):
    """Rotate-half RoPE. x (S, H, D), positions (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(cfg, p, h, cast):
    if cfg["hidden_act"] == "silu":
        return cast(jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]
    a = h @ p["w_up"] + p["b_up"]
    g = 0.5 * a * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                  * (a + 0.044715 * a ** 3)))
    return cast(g) @ p["w_down"] + p["b_down"]


def _layer(cfg, q_block, cast, p, x):
    """One decoder layer over one sequence x (S, d), causal: the layer's
    output and its K (after RoPE) and V, (S, Hkv, D) each, as a KV cache
    holds them. ``cast`` rounds every matmul input (identity for the float32
    reference); norms and biases stay in float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    p = jax.tree.map(lambda a: cast(a) if a.ndim >= 2 else a, p)
    S = x.shape[0]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    pos = jnp.arange(S)
    a = p["mixer"]
    h = cast(_norm(cfg, x, p["norm1"]))
    q = jnp.einsum("sd,dhe->she", h, a["wq"])
    k = jnp.einsum("sd,dhe->she", h, a["wk"])
    v = jnp.einsum("sd,dhe->she", h, a["wv"])
    if cfg["qkv_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = _rope(q, pos, cfg["rope_theta"]).reshape(S, hk, hq // hk, hd)
    k = _rope(k, pos, cfg["rope_theta"])
    kc, vc = cast(k), cast(v)
    outs = []
    for s0 in range(0, S, q_block):  # causal attention, a block of queries
        qb = cast(q[s0:s0 + q_block])
        sc = jnp.einsum("qngd,knd->ngqk", qb, kc) / math.sqrt(hd)
        ok = pos[None, :] <= (s0 + jnp.arange(qb.shape[0]))[:, None]
        pr = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("ngqk,knd->qngd", cast(pr), vc))
    o = cast(jnp.concatenate(outs).reshape(S, hq, hd))
    x = x + jnp.einsum("she,hed->sd", o, a["wo"])
    h2 = cast(_norm(cfg, x, p["norm2"]))
    return x + _mlp(cfg, p["ffn"], h2, cast), k, v


def _identity(a):
    return a


def fp8_cast(a):
    """Round to float8 e4m3 and back: the control's precision."""
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted(cfg_items: Tuple, control: bool):
    cfg = dict(cfg_items)
    cast = fp8_cast if control else _identity

    @jax.jit
    def embed(w, toks):
        return cast(w[toks].astype(jnp.float32))

    @jax.jit
    def layer(p, x):
        with jax.default_matmul_precision("highest"):
            return _layer(cfg, 512, cast, p, x)

    @jax.jit
    def head(params, x, ids):
        """Per position: the best logit, the second best, the logit of
        ``ids``, and the argmax."""
        with jax.default_matmul_precision("highest"):
            h = _norm(cfg, x, jax.tree.map(
                lambda a: a.astype(jnp.float32), params["final_norm"]))
            w = (params["embed"]["w"].T if cfg["tie_word_embeddings"]
                 else params["head"]["w"]).astype(jnp.float32)
            logits = cast(h) @ cast(w)
        top2 = jax.lax.top_k(logits, 2)[0]
        picked = jnp.take_along_axis(logits, ids[:, None], -1)[:, 0]
        return top2[:, 0], top2[:, 1], picked, jnp.argmax(logits, -1)

    return embed, layer, head


def _hashable(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def ref_pass(cfg: Dict[str, Any], params, tokens: np.ndarray, *,
             control: bool = False, keep_kv: bool = False, pad_to: int = 512):
    """The last layer's output for one sequence, computed layer by layer in
    float32 (``control``: every matmul input rounded to float8), and with
    ``keep_kv`` each layer's (K, V) at the real positions as numpy arrays.
    The sequence is padded at its end to a multiple of ``pad_to``, which
    causal attention leaves without effect on the real positions."""
    embed, layer, _ = _jitted(_hashable(cfg), control)
    n = len(tokens)
    Sp = -(-n // pad_to) * pad_to
    toks = np.zeros(Sp, np.int32)
    toks[:n] = tokens
    x = embed(params["embed"]["w"], toks)
    kv = []
    for p in params["layers"]:
        x, k, v = layer(p, x)
        if keep_kv:
            kv.append((np.asarray(k[:n]), np.asarray(v[:n])))
    return x, kv


def _head(cfg, params, x, ids: np.ndarray, control: bool = False):
    _, _, head = _jitted(_hashable(cfg), control)
    full = np.zeros(x.shape[0], np.int32)
    full[:len(ids)] = ids
    return [np.asarray(a) for a in head(params, x, full)]


def rel_err(a: np.ndarray, ref: np.ndarray) -> float:
    """||a - ref|| / ||ref||, in float64."""
    a, ref = a.astype(np.float64), ref.astype(np.float64)
    return float(np.linalg.norm(a - ref) / max(np.linalg.norm(ref), 1e-30))


def judge(cfg, params, seq: np.ndarray, served: List[int], *,
          kv=None, control: bool = False) -> Dict[str, np.ndarray]:
    """Reference readings of one request. ``seq`` is the tokens whose KV the
    program holds (prompt, then served tokens); ``served`` the tokens served
    at its last ``len(served)`` positions; ``kv`` the program's per-layer
    (K, V) over ``seq``, or None.

    - ``gap``: for each served token, how far its reference logit lies below
      the reference's best at that position;
    - ``margin``: the reference's best logit less its second, there;
    - ``kv_err``: per layer, the larger of K's and V's error relative to the
      reference's (only where ``kv`` is given).

    With ``control`` the float8 reference stands in the program's place: the
    tokens judged are those it puts first at the same positions, and the KV
    judged is its own."""
    n = len(seq)
    rows = np.arange(n - len(served), n)
    want_kv = kv is not None
    x, ref_kv = ref_pass(cfg, params, seq, keep_kv=want_kv)
    ids = np.zeros(x.shape[0], np.int32)
    ids[rows] = served
    if control:
        xc, kv = ref_pass(cfg, params, seq, control=True, keep_kv=want_kv)
        ids[rows] = _head(cfg, params, xc, ids, control=True)[3][rows]
    best, second, picked, _ = _head(cfg, params, x, ids)
    out = {"gap": (best - picked)[rows], "margin": (best - second)[rows]}
    if want_kv:
        out["kv_err"] = np.array([max(rel_err(k, rk), rel_err(v, rv))
                                  for (k, v), (rk, rv) in zip(kv, ref_kv)])
    return out
