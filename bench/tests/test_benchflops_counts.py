"""FLOP and byte counts against the program's own parameter count and a
hand count at one small shape; the peaks table."""
import json

import jax
import pytest

from bench import flops, model, spec

CONFIGS = ["yi-6b", "starcoder2-7b-d22"]


def load(name):
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_param_count_matches_the_program(name):
    from repro.models import init_params_specs

    cfg = load(name)
    arch = model.arch_config(cfg)
    tree = init_params_specs(arch)
    leaves = {jax.tree_util.keystr(k): v.shape for k, v in
              jax.tree_util.tree_flatten_with_path(tree)[0]}
    mine = {jax.tree_util.keystr(k): v[0] for k, v in
            jax.tree_util.tree_flatten_with_path(
                model.leaf_specs(cfg), is_leaf=model._is_spec)[0]}
    assert mine == leaves  # the served tree, leaf for leaf
    n = model.param_count(cfg)
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    # ArchConfig.param_count counts one vector per norm and no MLP biases
    extra = 0
    if cfg["norm_type"] == "layernorm":
        extra += (2 * L + 1) * d
    if cfg["hidden_act"] != "silu":
        extra += L * (cfg["intermediate_size"] + d)
    assert n == arch.param_count() + extra
    # matmul weights: everything but norms, biases and the embedding table
    vec = sum(v[0][0] for v in jax.tree.leaves(model.leaf_specs(cfg),
                                               is_leaf=model._is_spec)
              if len(v[0]) == 1)
    qkv_b = (L * (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"])
             * cfg["head_dim"] if cfg["qkv_bias"] else 0)
    embed = cfg["vocab_size"] * d
    assert (L * flops.layer_matmul_params(cfg) + embed * 2
            == n - vec - qkv_b)


def test_published_sizes():
    assert model.param_count(load("yi-6b")) == 6_061_035_520
    sc = load("starcoder2-7b-d22")
    assert flops.layer_matmul_params(sc) == 217_055_232


SMALL = {"num_hidden_layers": 2, "hidden_size": 8, "intermediate_size": 16,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
         "vocab_size": 10, "hidden_act": "silu"}


def test_hand_count_at_a_small_shape():
    # per layer: q 8*4*2 + o 4*2*8 = 128, k and v 2 * 8*2*2 = 64, MLP 3*8*16
    assert flops.layer_matmul_params(SMALL) == 128 + 64 + 384
    per_tok = 2 * 2 * 576
    attn = 4 * 2 * 4 * 2  # per key, both layers
    assert flops.decode_flops(SMALL, [3, 5]) == (
        2 * (per_tok + 2 * 8 * 10) + attn * (3 + 5))
    # positions 4, 5, 6 attend 5, 6, 7 keys; the last one's logits count
    assert flops.prefill_flops(SMALL, 4, 7, True) == (
        3 * per_tok + attn * (5 + 6 + 7) + 160)
    assert flops.prefill_flops(SMALL, 0, 1, False) == per_tok + attn
    f, b = flops.paged_attention_cost(4, 2, 2, [3, 5], 2, 4)
    assert f == 4 * 4 * 2 * 8
    assert b == 2 * 2 * 2 * 8 * 4 + 2 * 2 * 4 * 2 * 2


def test_peaks_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
    t, bound = flops.roofline_seconds(197e12, 819e9 / 2, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.roofline_seconds(1.0, 819e9, p)
    assert (t, bound) == (1.0, "memory")
