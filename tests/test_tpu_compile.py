"""The Pallas kernels compile for a TPU v5e at the widths they run at, and
the KV pool's programs compile to in-place updates of the pool.

Nothing runs: each test lowers a kernel for a described (not attached) v5e
chip and compiles it with the TPU compiler, which refuses what interpret
mode accepts (misaligned blocks, more VMEM than a kernel may use). The
topology is described inside a fixture, so only the worker that runs these
tests loads the TPU library; where it cannot be described the tests skip.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.paged_attention import paged_attention
from repro.kernels.qv_gate import apply_two_qubit_gate
from repro.kernels.stencil5 import stencil5
from repro.serve import paged


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache, so keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# (H, Hkv, D): yi-6b decode widths, and a GQA shape with D=64
@pytest.mark.parametrize("H,Hkv,D", [(32, 4, 128), (24, 8, 64)])
@pytest.mark.parametrize("q_dtype,kv_dtype", [
    (jnp.bfloat16, jnp.bfloat16),
    (jnp.float32, jnp.float32),
    (jnp.bfloat16, jnp.float32),  # the serve engine: bf16 model, f32 pool
])
def test_paged_attention_compiles(one_chip, H, Hkv, D, q_dtype, kv_dtype):
    B, PS, NP = 8, 64, 16
    P = B * NP + 1
    args = (_sds((B, H, D), q_dtype, one_chip),
            _sds((P, Hkv, PS, D), kv_dtype, one_chip),
            _sds((P, Hkv, PS, D), kv_dtype, one_chip),
            _sds((B, NP), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip))
    _assert_kernel(paged_attention.lower(*args, interpret=False).compile())


def test_stencil5_compiles_at_4096(one_chip):
    grid = _sds((4096, 4096), jnp.float32, one_chip)
    _assert_kernel(stencil5.lower(grid, 0.1, interpret=False).compile())


def test_qv_gate_compiles_at_qsim_fig3(one_chip):
    n = 16  # apps/qsim.py "fig3" size
    state = _sds((2 ** n,), jnp.complex64, one_chip)
    gate = _sds((4, 4), jnp.complex64, one_chip)
    _assert_kernel(apply_two_qubit_gate.lower(
        state, gate, 3, 11, n, interpret=False).compile())


# yi-6b's f32 pool (16 seqs x 1,024 tokens in pages of 64, 4 KV heads of
# 128); T tokens: a decode batch, a prefill chunk, a 3,584-token prefix
@pytest.mark.parametrize("program,T", [("kv_write", 16), ("kv_write", 128),
                                       ("kv_gather", 3584)])
def test_kv_pool_programs_leave_the_pool_in_place(one_chip, program, T):
    pool = _sds((257, 4, 64, 128), jnp.float32, one_chip)
    idx = _sds((T,), jnp.int32, one_chip)
    args = [pool, pool, idx, idx]
    if program == "kv_write":
        args += [_sds((T, 1, 4, 128), jnp.bfloat16, one_chip)] * 2
    hlo = getattr(paged, program).lower(*args).compile().as_text()
    # no copy of a whole pool (a relayout around the scatter or gather)
    assert "f32[257,4,64,128]" not in "".join(
        line for line in hlo.splitlines() if " copy(" in line)
    if program == "kv_write":  # donated: the pools come back in place
        assert "input_output_alias={ {0}: (0, {}" in hlo


# mellum2 decode: 32 q / 4 kv heads of 128, 8 x 4,096 tokens in pages of
# 64, the sliding layers' 1,024-token window
def test_windowed_paged_attention_compiles(one_chip):
    B, PS, NP = 8, 64, 64
    P = B * NP + 1
    args = (_sds((B, 32, 128), jnp.bfloat16, one_chip),
            _sds((P, 4, PS, 128), jnp.float32, one_chip),
            _sds((P, 4, PS, 128), jnp.float32, one_chip),
            _sds((B, NP), jnp.int32, one_chip),
            _sds((B,), jnp.int32, one_chip))
    _assert_kernel(paged_attention.lower(*args, window=1024,
                                         interpret=False).compile())


# mellum2's expert layer at published widths: 64 experts of 2,304 x 896,
# top-8, for a decode batch of 8 and a 128-token prefill chunk
@pytest.mark.parametrize("T", [8, 128])
def test_dropless_experts_compile_to_grouped_matmuls(one_chip, T):
    import dataclasses

    from repro.configs import get_config
    from repro.models import moe

    cfg = dataclasses.replace(get_config("mellum2-12b-a2.5b"), num_layers=1)
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    p = {"router": _sds((d, E), jnp.bfloat16, one_chip),
         "w_gate": _sds((E, d, f), jnp.bfloat16, one_chip),
         "w_up": _sds((E, d, f), jnp.bfloat16, one_chip),
         "w_down": _sds((E, f, d), jnp.bfloat16, one_chip)}
    x = _sds((1, T, d), jnp.bfloat16, one_chip)
    hlo = jax.jit(lambda p, x: moe.moe_apply_dropless(cfg, p, x)).lower(
        p, x).compile().as_text()
    # one grouped matmul a projection, under the operation name the device
    # trace shows and bench/metrics/expert_ffn_roofline.py reads
    assert len(re.findall(r"%ragged-dot-none[.\d]* = \S+ custom-call\(",
                          hlo)) == 3


def _spec_tree(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, sharding), tree)


# the serve engine's folded programs (a layer's rest and the next layer's
# QKV in one program) at published widths in bf16: yi-6b's dense layer,
# and mellum2's last sliding layer followed by its full one; a decode batch
# of 16 and a 128-token prefill chunk over a 1,024-token prefix
@pytest.mark.parametrize("name,kinds", [
    ("yi-6b", ("attention", "attention")),
    ("mellum2-12b-a2.5b", ("local", "attention"))])
def test_folded_serve_programs_compile(one_chip, name, kinds):
    import dataclasses

    from repro.configs import get_config
    from repro.models import init_params_specs
    from repro.serve import ServeEngine

    cfg = dataclasses.replace(get_config(name), num_layers=4)
    i = 2  # layer 2 of kinds[0], followed by layer 3 of kinds[1]
    assert cfg.layer_kinds()[i:i + 2] == list(kinds)
    params = _spec_tree(init_params_specs(cfg), one_chip)
    eng = ServeEngine(cfg, params, max_seqs=1, max_len=64, page_size=64)
    p, p_next = params["layers"][i], params["layers"][i + 1]
    d, H, D = cfg.d_model, eng.layout.n_q_eff, cfg.head_dim
    N = eng.layout.n_kv_eff
    bf16 = jnp.bfloat16
    B, S, E = 16, 128, 1024
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)
    decode = eng._decode_fold[kinds[1]].lower(
        p, _sds((B, 1, d), bf16, one_chip), _sds((B, H, D), bf16, one_chip),
        None if not cfg.is_moe else i32(), p_next, i32(B, 1))
    x = _sds((1, S, d), bf16, one_chip)
    q = jax.eval_shape(eng._qkv[kinds[0]], p, x, i32(S))[0]
    prefill = eng._prefill_fold[kinds].lower(
        p, x, _sds(q.shape, q.dtype, one_chip),
        _sds((E, N, D), jnp.float32, one_chip),
        _sds((E, N, D), jnp.float32, one_chip), i32(S), i32(E),
        None if not cfg.is_moe else i32(), p_next)
    for low, name in ((decode, "layer_rest_qkv"),
                      (prefill, "prefill_layer_rest_qkv")):
        assert low.as_text().startswith(f"module @jit_{name} ")
        hlo = low.compile().as_text()
        # the expert matmuls stay grouped matmuls inside the program
        n = len(re.findall(r"%ragged-dot-none[.\d]* = \S+ custom-call\(", hlo))
        assert n == (3 if cfg.is_moe else 0), n
