"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp ref oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.kernels.qv_gate import apply_two_qubit_gate, apply_two_qubit_gate_ref
from repro.kernels.stencil5 import stencil5, stencil5_ref


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("B,S,H,Hkv,D", [
    (2, 256, 8, 2, 64),
    (1, 512, 4, 4, 128),
    (2, 128, 16, 1, 64),
    (1, 256, 6, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 64])
def test_flash_attention(B, S, H, Hkv, D, dtype, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), dtype)
    o = flash_attention(q, k, v, window=window, block_q=64, block_k=64,
                        interpret=True)
    r = flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=_tol(dtype))


@pytest.mark.parametrize("B,H,Hkv,D,P,PS,NP", [
    (2, 8, 2, 64, 16, 16, 4),
    (3, 4, 4, 128, 32, 8, 6),
    (1, 16, 1, 64, 8, 32, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(B, H, Hkv, D, P, PS, NP, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (P, Hkv, PS, D), dtype)
    vp = jax.random.normal(ks[2], (P, Hkv, PS, D), dtype)
    pt = jax.random.permutation(ks[3], P)[:B * NP].reshape(B, NP).astype(jnp.int32)
    lengths = jnp.asarray([NP * PS - 3] + [max(1, (NP - 1) * PS)] * (B - 1),
                          jnp.int32)[:B]
    o = paged_attention(q, kp, vp, pt, lengths, interpret=True)
    r = paged_attention_ref(q, kp, vp, pt, lengths)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=_tol(dtype))


def _pool_case(B, H, Hkv, D, P, PS, NP, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (P, Hkv, PS, D), dtype)
    vp = jax.random.normal(ks[2], (P, Hkv, PS, D), dtype)
    pt = jax.random.permutation(ks[3], P)[:B * NP].reshape(B, NP).astype(jnp.int32)
    return q, kp, vp, pt


# lengths inside the window, ending on and past page edges, and far past it
@pytest.mark.parametrize("window", [16, 24, 40])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_window(window, dtype):
    q, kp, vp, pt = _pool_case(4, 8, 2, 64, 32, 8, 8, dtype)
    lengths = jnp.asarray([5, 16, 41, 64], jnp.int32)
    o = paged_attention(q, kp, vp, pt, lengths, window=window, interpret=True)
    r = paged_attention_ref(q, kp, vp, pt, lengths, window=window)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=_tol(dtype))
    # the window binds: attending every key gives another result
    full = paged_attention_ref(q, kp, vp, pt, lengths)
    assert not np.allclose(np.asarray(full[2:], np.float32),
                           np.asarray(r[2:], np.float32), atol=_tol(dtype))


def test_paged_attention_window_zero_is_unwindowed():
    """window=0 attends every key: bit for bit what a window no sequence
    reaches gives (the pages before it are skipped, nothing else moves)."""
    q, kp, vp, pt = _pool_case(3, 8, 2, 64, 32, 8, 8, jnp.bfloat16)
    lengths = jnp.asarray([7, 33, 64], jnp.int32)
    o0 = paged_attention(q, kp, vp, pt, lengths, interpret=True)
    o_wide = paged_attention(q, kp, vp, pt, lengths, window=64, interpret=True)
    assert np.array_equal(np.asarray(o0), np.asarray(o_wide))


@pytest.mark.parametrize("n,q1,q2", [(10, 0, 1), (12, 3, 9), (12, 11, 2), (11, 7, 6)])
def test_qv_gate(n, q1, q2):
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    st = jax.random.normal(k1, (2 ** n,)) + 1j * jax.random.normal(k2, (2 ** n,))
    st = (st / jnp.linalg.norm(st)).astype(jnp.complex64)
    g = jax.random.normal(k1, (4, 4)) + 1j * jax.random.normal(k2, (4, 4))
    u, _ = jnp.linalg.qr(g)
    u = u.astype(jnp.complex64)
    o = apply_two_qubit_gate(st, u, q1, q2, n, interpret=True)
    r = apply_two_qubit_gate_ref(st, u, q1, q2, n)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-5)
    assert abs(float(jnp.linalg.norm(o)) - 1.0) < 1e-5  # unitarity


@pytest.mark.parametrize("H,W,th", [(256, 128, 64), (128, 256, 128), (512, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_stencil5(H, W, th, dtype):
    g = jax.random.normal(jax.random.PRNGKey(3), (H, W), dtype)
    o = stencil5(g, 0.1, tile_h=th, interpret=True)
    r = stencil5_ref(g, 0.1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-6)


def test_flash_matches_model_blocked_path():
    """The Pallas kernel and the model's pure-JAX blocked path agree."""
    from repro.models.attention import _blocked_causal
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    B, S, N, P, D = 1, 256, 2, 3, 32
    q = jax.random.normal(ks[0], (B, S, N, P, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, N, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, N, D), jnp.float32)
    o_model = _blocked_causal(q, k, v, 64, 64, 0).reshape(B, S, N * P, D)
    o_kernel = flash_attention(q.reshape(B, S, N * P, D), k, v,
                               block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o_model), np.asarray(o_kernel), atol=2e-5)
