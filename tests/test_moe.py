"""MoE: routing exactness, capacity behavior, expert padding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.layers import RunPolicy
from repro.models import moe as moe_mod


def _cfg(n_experts=8, top_k=2):
    cfg = get_config("olmoe-1b-7b").reduced()
    return dataclasses.replace(cfg, num_experts=n_experts, top_k=top_k)


def test_moe_matches_dense_routing_at_high_capacity():
    """With capacity >= T, dense-dispatch MoE == explicit per-token gather."""
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    p = moe_mod.moe_init(cfg, key, jnp.float32, tp=1)
    x = jax.random.normal(key, (2, 16, cfg.d_model), jnp.float32)
    pol = RunPolicy(moe_capacity_factor=64.0)  # no drops
    y, aux = moe_mod.moe_apply(cfg, p, x, pol, tp=1)

    # reference: per-token explicit computation
    xt = x.reshape(-1, cfg.d_model)
    logits = xt @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    g, idx = jax.lax.top_k(probs, cfg.top_k)
    g = g / g.sum(-1, keepdims=True)
    ref = jnp.zeros_like(xt)
    for t in range(xt.shape[0]):
        acc = jnp.zeros((cfg.d_model,))
        for s in range(cfg.top_k):
            e = int(idx[t, s])
            h = jax.nn.silu(xt[t] @ p["w_gate"][e]) * (xt[t] @ p["w_up"][e])
            acc = acc + g[t, s] * (h @ p["w_down"][e])
        ref = ref.at[t].set(acc)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(ref), atol=1e-4)
    assert float(aux) > 0


def test_expert_padding_never_routed():
    """granite: 40 experts padded to 48 — pads get -inf logits, zero traffic."""
    cfg = _cfg(n_experts=6, top_k=2)  # 6 pads to 8 at tp=8
    p = moe_mod.moe_init(cfg, jax.random.PRNGKey(0), jnp.float32, tp=8)
    assert p["router"].shape[1] == 8
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    xt = x.reshape(-1, cfg.d_model)
    logits = (xt @ p["router"]).astype(jnp.float32)
    pad = jnp.arange(8) >= 6
    logits = jnp.where(pad[None], moe_mod.NEG_INF, logits)
    _, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    assert int(jnp.max(idx)) < 6
    # and apply() equals the tp=1 (unpadded) result
    p1 = moe_mod.moe_init(cfg, jax.random.PRNGKey(0), jnp.float32, tp=1)
    y8, _ = moe_mod.moe_apply(cfg, p, x, RunPolicy(), tp=8)
    y1, _ = moe_mod.moe_apply(cfg, p1, x, RunPolicy(), tp=1)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y1), atol=1e-5)


def test_capacity_drops_pass_through():
    """Over-capacity tokens are dropped (residual passes through unchanged)."""
    cfg = _cfg(n_experts=2, top_k=1)
    p = moe_mod.moe_init(cfg, jax.random.PRNGKey(0), jnp.float32, tp=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model))
    tight = RunPolicy(moe_capacity_factor=0.25)
    loose = RunPolicy(moe_capacity_factor=64.0)
    y_t, _ = moe_mod.moe_apply(cfg, p, x, tight, tp=1)
    y_l, _ = moe_mod.moe_apply(cfg, p, x, loose, tp=1)
    # tight capacity zeroes some tokens' outputs
    zt = np.asarray(jnp.sum(jnp.abs(y_t), axis=-1))[0]
    zl = np.asarray(jnp.sum(jnp.abs(y_l), axis=-1))[0]
    assert (zt == 0).sum() > (zl == 0).sum()


def test_sorted_dispatch_matches_dense():
    """Beyond-paper sorted (scatter) dispatch == dense GShard dispatch at
    every capacity regime, including identical drop priority."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = _cfg(n_experts=8, top_k=2)
    p = moe_mod.moe_init(cfg, jax.random.PRNGKey(0), jnp.float32, tp=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    for cf in (64.0, 1.25, 0.5):
        yd, _ = moe_mod.moe_apply_dense(cfg, p, x, RunPolicy(moe_capacity_factor=cf))
        ys, _ = moe_mod.moe_apply_sorted(cfg, p, x, RunPolicy(moe_capacity_factor=cf))
        np.testing.assert_allclose(np.asarray(yd), np.asarray(ys), atol=2e-5)


def _per_token(cfg, p, xt):
    """Each token through its top-k experts, one at a time, in float32."""
    logits = xt @ p["router"][:, :cfg.num_experts]
    g, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.top_k)
    g = g / g.sum(-1, keepdims=True)
    out = []
    for t in range(xt.shape[0]):
        acc = jnp.zeros((cfg.d_model,))
        for s in range(cfg.top_k):
            e = int(idx[t, s])
            h = jax.nn.silu(xt[t] @ p["w_gate"][e]) * (xt[t] @ p["w_up"][e])
            acc = acc + g[t, s] * (h @ p["w_down"][e])
        out.append(acc)
    return jnp.stack(out), len(set(np.asarray(idx).ravel().tolist()))


@pytest.mark.parametrize("skew", [0.0, 8.0])
def test_dropless_matches_per_token_loop(skew):
    """Every token reaches all its top-k experts, also when most tokens
    route to one expert (``skew``: a bias on expert 0's router column, past
    any capacity the capacity paths would give it)."""
    cfg = _cfg(n_experts=16, top_k=4)
    p = moe_mod.moe_init(cfg, jax.random.PRNGKey(3), jnp.float32, tp=1)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 11, cfg.d_model))
    if skew:
        p["router"] = p["router"].at[:, 0].add(skew * x.mean((0, 1)) /
                                                jnp.sum(x.mean((0, 1)) ** 2))
    y, hit = moe_mod.moe_apply_dropless(cfg, p, x)
    ref, n_hit = _per_token(cfg, p, x.reshape(-1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)),
                               np.asarray(ref), atol=1e-5)
    assert int(hit) == n_hit
    if skew:  # the capacity path drops what the dropless one keeps
        y_cap, _ = moe_mod.moe_apply(cfg, p, x, RunPolicy(), tp=1)
        assert not np.allclose(np.asarray(y_cap), np.asarray(y), atol=1e-3)


def test_dropless_never_routes_to_padded_experts():
    cfg = _cfg(n_experts=6, top_k=2)
    p8 = moe_mod.moe_init(cfg, jax.random.PRNGKey(0), jnp.float32, tp=8)
    p1 = moe_mod.moe_init(cfg, jax.random.PRNGKey(0), jnp.float32, tp=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y8, hit8 = moe_mod.moe_apply_dropless(cfg, p8, x)
    y1, hit1 = moe_mod.moe_apply_dropless(cfg, p1, x)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y1), atol=1e-6)
    assert int(hit8) == int(hit1) <= 6


def test_dropless_output_is_independent_of_batch_mates():
    """A token's output is the same alone as in any batch (bf16 weights)."""
    cfg = _cfg(n_experts=16, top_k=4)
    p = moe_mod.moe_init(cfg, jax.random.PRNGKey(5), jnp.bfloat16, tp=1)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 24, cfg.d_model),
                          jnp.bfloat16)
    together, _ = moe_mod.moe_apply_dropless(cfg, p, x)
    alone = [moe_mod.moe_apply_dropless(cfg, p, x[:, i:i + 1])[0]
             for i in range(x.shape[1])]
    np.testing.assert_array_equal(np.asarray(together, np.float32),
                                  np.asarray(jnp.concatenate(alone, 1),
                                             np.float32))
