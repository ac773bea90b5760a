"""Autoregressive consistency: prefill+decode == full forward; chunked == ref."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import RunPolicy, decode_step, forward, init_params, prefill
from repro.models.cache import init_cache
from repro.models.rwkv import wkv6_chunked, wkv6_ref
from repro.models.attention import attn_apply, attn_decode
from repro.models.layout import HeadLayout


@pytest.mark.parametrize("arch", ["yi-6b", "qwen2.5-32b", "recurrentgemma-2b",
                                  "rwkv6-1.6b", "olmoe-1b-7b", "musicgen-medium",
                                  "mellum2-12b-a2.5b"])
def test_prefill_then_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    # high capacity factor: capacity-MoE token drops depend on batch size, so
    # exact prefill/decode == forward equality needs the no-drop regime
    pol = RunPolicy(moe_capacity_factor=1e9)
    B, S = 2, 24
    key = jax.random.PRNGKey(1)
    if cfg.input_kind == "embeddings":
        toks = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
        tok_at = lambda i: toks[:, i:i + 1, :]
    else:
        toks = jax.random.randint(key, (B, S), 2, cfg.vocab_size)
        tok_at = lambda i: toks[:, i:i + 1]

    full_logits, _ = forward(cfg, params, toks, pol)

    # prefill the first S-4 tokens, then decode the rest one-by-one
    Spre = S - 4
    pre = toks[:, :Spre]
    logits_last, _ = prefill(cfg, params, pre, pol)
    np.testing.assert_allclose(np.asarray(logits_last[:, 0]),
                               np.asarray(full_logits[:, Spre - 1]),
                               atol=2e-3, rtol=1e-3)

    # decode from scratch: feed tokens sequentially through decode_step
    cache = init_cache(cfg, B, S + 2, tp=1, dtype=jnp.float32)
    step = jax.jit(lambda p, t, ps, c: decode_step(cfg, p, t, ps, c, pol))
    for i in range(S):
        lg, cache = step(params, tok_at(i), jnp.full((B,), i, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full_logits[:, i]),
                                   atol=2e-3, rtol=1e-3)


@pytest.mark.parametrize("B,S,H,K,chunk", [(2, 64, 2, 8, 16), (1, 96, 4, 16, 32),
                                           (2, 33, 2, 8, 16)])
def test_wkv6_chunked_vs_ref(B, S, H, K, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (B, S, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, K)) * 0.5
    wlog = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) - 2.0)
    u = 0.3 * jax.random.normal(ks[4], (H, K))
    s0 = jnp.zeros((B, H, K, K))
    y1, sT1 = wkv6_ref(r, k, v, wlog, u, s0)
    y2, sT2 = wkv6_chunked(r, k, v, wlog, u, s0, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sT1), np.asarray(sT2), atol=2e-5)


def test_sliding_window_decode_ring_buffer():
    """Ring-buffer decode == full-cache decode with window mask."""
    cfg = dataclasses.replace(get_config("recurrentgemma-2b").reduced(),
                              local_window=8)
    lay = HeadLayout.make(cfg.num_heads, cfg.num_kv_heads, 1)
    key = jax.random.PRNGKey(0)
    from repro.models.attention import attn_init
    p = attn_init(cfg, lay, key, jnp.float32)
    pol = RunPolicy()
    B, S, W = 1, 24, 8
    x = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
    full, _ = attn_apply(cfg, p, x, lay, pol, window=W)
    ring = {"k": jnp.zeros((B, W, lay.n_kv_eff, cfg.head_dim)),
            "v": jnp.zeros((B, W, lay.n_kv_eff, cfg.head_dim))}
    for i in range(S):
        o, ring = attn_decode(cfg, p, x[:, i:i + 1], lay, pol,
                              jnp.asarray([i], jnp.int32), ring, window=W)
        np.testing.assert_allclose(np.asarray(o[:, 0]), np.asarray(full[:, i]),
                                   atol=1e-4)


def test_int8_kv_cache_decode_close_to_fp():
    """int8 KV cache (decode memory-term lever): output distribution within
    quantization tolerance of the fp cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import RunPolicy, decode_step, forward, init_params
    from repro.models.cache import init_cache

    cfg = get_config("yi-6b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    pol = RunPolicy()
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 2, cfg.vocab_size)
    full, _ = forward(cfg, params, toks, pol)
    cache = init_cache(cfg, B, S + 2, tp=1, dtype=jnp.float32, kv_quant=True)
    step = jax.jit(lambda p, t, ps, c: decode_step(cfg, p, t, ps, c, pol))
    for i in range(S):
        lg, cache = step(params, toks[:, i:i + 1], jnp.full((B,), i, jnp.int32), cache)
        np.testing.assert_allclose(
            np.asarray(jax.nn.softmax(lg[:, 0])),
            np.asarray(jax.nn.softmax(full[:, i])), atol=0.05)


def test_yarn_frequencies_worked_by_hand():
    """Mellum2's full layers: theta 5e5, head 128, factor 16 over 8,192
    positions, beta 32 / 1. The correction dims are
    128 ln(8192 / (2 pi b)) / (2 ln 5e5): 18.08 for b = 32 (floor 18) and
    34.98 for b = 1 (ceil 35). Below dim 18 a frequency keeps
    theta^(-2i/128), from 35 on it is divided by 16, and between them the
    ramp (i - 18) / 17 blends the two; cos and sin scale by
    0.1 ln 16 + 1."""
    from repro.models.layers import rope_apply, yarn_inv_freq

    cfg = get_config("mellum2-12b-a2.5b")
    inv, mscale = yarn_inv_freq(cfg.global_yarn, 128, cfg.rope_theta)
    assert inv.shape == (64,) and inv[0] == 1.0
    np.testing.assert_allclose(inv[10], 0.12868737343265052, rtol=1e-12)
    np.testing.assert_allclose(inv[26], 0.0027043825167258227, rtol=1e-12)
    np.testing.assert_allclose(inv[63], 1.5344629944572555e-07, rtol=1e-12)
    np.testing.assert_allclose(inv[18], 500000.0 ** (-36 / 128), rtol=1e-12)
    np.testing.assert_allclose(inv[35], 500000.0 ** (-70 / 128) / 16, rtol=1e-12)
    assert mscale == 1.2772588722239782
    # rope_apply: at position 1, dim 0 turns by 1 rad and the pair's norm
    # grows by the attention factor; sliding layers keep plain RoPE
    x = jnp.zeros((1, 1, 128)).at[0, 0, 0].set(1.0)
    y = np.asarray(rope_apply(x, jnp.ones((1,), jnp.int32), cfg.rope_theta,
                              cfg.global_yarn))[0, 0]
    np.testing.assert_allclose([y[0], y[64]],
                               [mscale * np.cos(1.0), mscale * np.sin(1.0)],
                               rtol=1e-6)
    assert cfg.yarn_for("local") is None
    assert cfg.yarn_for("attention") is cfg.global_yarn
