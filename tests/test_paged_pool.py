"""The KV pool's jitted, donated write and gather: the same bits as the
eager fancy-index formula, one index upload a decode step for all layers,
and one compile per shape whatever the layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.models.cache import kv_head_layout
from repro.serve import PagedKVCache, ServeEngine
from repro.serve import paged


@pytest.fixture(scope="module")
def model():
    cfg = get_config("yi-6b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def filled_cache(cfg, page_size=8, seed=0):
    """A two-sequence pool whose every slot already holds noise, so a write
    that touched a slot it should not would show."""
    c = PagedKVCache(cfg, kv_head_layout(cfg, 1), max_seqs=2, max_len=40,
                     page_size=page_size)
    rng = np.random.default_rng(seed)
    shape = c.k_pools[0].shape
    c.k_pools = [jnp.asarray(rng.standard_normal(shape, np.float32))
                 for _ in c.k_pools]
    c.v_pools = [jnp.asarray(rng.standard_normal(shape, np.float32))
                 for _ in c.v_pools]
    return c, rng


def host_pools(c):
    return ([np.asarray(k) for k in c.k_pools],
            [np.asarray(v) for v in c.v_pools])


def eager_set(pool, pids, slots, x):
    """The eager formula the jitted write replaces."""
    return np.asarray(jnp.asarray(pool).at[pids, :, slots].set(x))


def assert_pools(c, ks, vs):
    for layer in range(len(ks)):
        np.testing.assert_array_equal(np.asarray(c.k_pools[layer]), ks[layer])
        np.testing.assert_array_equal(np.asarray(c.v_pools[layer]), vs[layer])


def test_decode_batch_write_matches_the_eager_scatter(model):
    cfg = model[0]
    c, rng = filled_cache(cfg)
    sids = [c.new_seq(), c.new_seq()]
    pos = [5, 11]  # the second crosses into its second page
    for s, p in zip(sids, pos):
        c.alloc_range(s, 0, p + 1)
    N, D = c.layout.n_kv_eff, cfg.head_dim
    pids = c.page_table[sids, np.asarray(pos) // c.page_size]
    slots = np.asarray(pos) % c.page_size
    ks, vs = host_pools(c)
    for layer in range(cfg.num_layers):
        k = rng.standard_normal((2, 1, N, D), np.float32)
        v = rng.standard_normal((2, 1, N, D), np.float32)
        ks[layer] = eager_set(ks[layer], pids, slots, k[:, 0])
        vs[layer] = eager_set(vs[layer], pids, slots, v[:, 0])
        c.write_token(sids, layer, jnp.asarray(k), jnp.asarray(v), pos)
    assert_pools(c, ks, vs)


def test_prefill_chunk_ending_mid_page_writes_only_its_slots(model):
    cfg = model[0]
    c, rng = filled_cache(cfg)
    sid = c.new_seq()
    start, S = 3, 10  # positions 3..12: the tail page is written to slot 4
    c.alloc_range(sid, 0, start + S)
    N, D = c.layout.n_kv_eff, cfg.head_dim
    pos = start + np.arange(S)
    pids = c.page_table[sid, pos // c.page_size]
    slots = pos % c.page_size
    ks, vs = host_pools(c)
    for layer in range(cfg.num_layers):
        k = rng.standard_normal((1, S, N, D), np.float32)
        v = rng.standard_normal((1, S, N, D), np.float32)
        ks[layer] = eager_set(ks[layer], pids, slots, k[0])
        vs[layer] = eager_set(vs[layer], pids, slots, v[0])
        c.write_at(sid, layer, jnp.asarray(k), jnp.asarray(v), start)
        gk, gv = c.gather_kv(sid, layer, start + S)
        allp, alls = c._flat_idx(sid, 0, start + S)
        np.testing.assert_array_equal(np.asarray(gk), ks[layer][allp, :, alls])
        np.testing.assert_array_equal(np.asarray(gv), vs[layer][allp, :, alls])
    assert_pools(c, ks, vs)


def test_swap_out_then_in_lands_the_same_bits(model):
    cfg = model[0]
    c, _ = filled_cache(cfg)
    sid = c.new_seq()
    c.alloc_range(sid, 0, 13)
    c.lengths[sid] = 13
    pids, slots = c._flat_idx(sid, 0, 13)
    ks, vs = host_pools(c)
    saved = c.swap_out(sid)
    assert saved["len"] == 13
    for layer in range(cfg.num_layers):
        np.testing.assert_array_equal(saved["k"][layer],
                                      ks[layer][pids, :, slots])
        np.testing.assert_array_equal(saved["v"][layer],
                                      vs[layer][pids, :, slots])
    # another sequence takes the freed pages first, so the swapped-in one
    # lands on other pages
    other = c.new_seq()
    c.alloc_range(other, 0, 8)
    back = c.swap_in(saved)
    new_pids, new_slots = c._flat_idx(back, 0, 13)
    assert not np.array_equal(new_pids, pids)
    for layer in range(cfg.num_layers):
        ks[layer] = eager_set(ks[layer], new_pids, new_slots, saved["k"][layer])
        vs[layer] = eager_set(vs[layer], new_pids, new_slots, saved["v"][layer])
    assert_pools(c, ks, vs)


def test_a_reused_sid_writes_to_its_new_pages(model):
    cfg = model[0]
    c, rng = filled_cache(cfg)
    N, D = c.layout.n_kv_eff, cfg.head_dim

    def kv():
        return [jnp.asarray(rng.standard_normal((1, 8, N, D), np.float32))
                for _ in range(2)]

    a, other = c.new_seq(), c.new_seq()
    c.alloc_range(a, 0, 8)
    c.write_at(a, 0, *kv(), 0)
    old = c.page_table[a, 0]
    c.release(a)
    c.alloc_range(other, 0, 8)  # takes the freed page
    b = c.new_seq()
    c.alloc_range(b, 0, 8)
    assert b == a and c.page_table[b, 0] != old
    ks, vs = host_pools(c)
    k, v = kv()
    pids, slots = c._flat_idx(b, 0, 8)
    ks[0] = eager_set(ks[0], pids, slots, k[0])
    vs[0] = eager_set(vs[0], pids, slots, v[0])
    c.write_at(b, 0, k, v, 0)  # the same sid, start and length as before
    assert_pools(c, ks, vs)


def test_a_decode_step_uploads_its_indices_once_for_all_layers(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, max_seqs=4, max_len=64, page_size=16)
    for n in (9, 17, 4):
        eng.add_request(np.arange(2, 2 + n), 6)
    eng.step()  # every prompt fits the first step's prefill budget
    assert all(r.state.value == "decoding" for r in eng.requests.values())
    c = eng.cache
    calls, uploads = c.kv_pool_calls, c.kv_index_uploads
    batches = eng.stats.decode_batches
    eng.step()  # a decode step alone
    assert eng.stats.decode_batches == batches + 1
    assert c.kv_pool_calls - calls == cfg.num_layers
    assert c.kv_index_uploads - uploads == 1


def test_the_write_compiles_once_per_shape_not_per_layer(model):
    cfg, params = model
    assert cfg.num_layers == 2
    # a pool shape (13 pages of 24) no other test builds
    eng = ServeEngine(cfg, params, max_seqs=3, max_len=72, page_size=24,
                      num_pages=13)
    for n in (5, 30, 11):
        eng.add_request(np.arange(3, 3 + n), 4)
    n0 = paged.kv_write._cache_size()
    # three prefill chunks (5, 30 and 11 tokens), then a decode batch of 3,
    # each written into both layers: four shapes, four programs
    eng.step()
    assert eng.stats.prefill_chunks == 3 and eng.stats.decode_batches == 1
    assert paged.kv_write._cache_size() == n0 + 4
    eng.step()  # that batch again: nothing new to compile
    assert eng.stats.decode_batches == 2
    assert paged.kv_write._cache_size() == n0 + 4
