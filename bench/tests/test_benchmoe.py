"""The sparse-expert configuration on the CPU at a tiny size: the engine's
logits against bench/model_moe.py's reference, a whole serve run (sound,
float8 control, and the timed path broken in each of the block's
mechanisms), the published sizes, and the FLOP counts against a hand count.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, flops_moe, model_moe, serve_moe, spec
from bench.run import execute

NAME = "mellum2-12b-a2.5b-d12"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# float32 weights: the program and the reference differ only in the order
# of float32 sums (measured below 1e-6), so a sound run reads about 1e-6 and
# the float8 control about 0.2 in kv_rel_err
LIMITS = {"logit_gap": 0.05, "kv_rel_err": 0.05, "short_requests": 0,
          "stuck_requests": 0, "unfinished": 0}


def load():
    return json.loads((spec.BENCH / "configs" / f"{NAME}.json").read_text())


def tiny(dtype="float32"):
    """One whole 3:1 period at toy widths, a 16-token window, 64 experts and
    top-8 as published."""
    cfg = load()
    cfg.update(num_hidden_layers=4, hidden_size=64, moe_intermediate_size=32,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=256, sliding_window=16, dtype=dtype)
    cfg["engine"] = dict(max_seqs=4, max_len=64, page_size=8,
                         memory_model="TPU_V5E")
    return cfg


def tiny_cell():
    # prompts past the window, varied lengths: requests finish in different
    # steps, so a window's close always leaves some in flight
    mix = {"kind": "closed_loop", "clients": 4, "requests": 1000,
           "lengths_seed": 3, "warm_steps": 4, "prompt_granule": 8,
           "check_requests": 3, "kv_requests": 2, "grace_s": 5.0,
           "prompt": {"median": 24, "sigma": 0.3, "min": 24, "max": 32},
           "output": {"median": 12, "sigma": 0.3, "min": 8, "max": 16}}
    bm = spec.load_benchmark()
    return spec.Cell("tiny", 1, tiny(), mix, bm["end_to_end"],
                     [m for m in bm["per_layer"]
                      if m["source"] != "device_trace"], LIMITS)


def test_engine_logits_match_the_reference():
    """Prefill then decode through the paged cache, every served position's
    logits against the reference's full forward pass. Prompts pass the
    window in the sliding layers; the batch's tokens reach most experts."""
    from repro.models.layers import apply_norm
    from repro.models.transformer import logits_out

    cfg = tiny()
    cfg["engine"]["memory_model"] = None
    params = model_moe.make_params(cfg, 2**33 + 5)
    eng = serve_moe.build_engine(cfg, params)
    got, at = {}, []
    nxt, dec, pre = eng._greedy_next, eng._decode_batch, eng._prefill_chunk_run

    def greedy(p, x):
        h = apply_norm(eng.cfg.norm, x[:, -1:], p["final_norm"])
        rows = np.asarray(logits_out(eng.cfg, p, h, eng.policy)[:, -1])
        got.update(zip(at[-1], rows))
        return nxt(p, x)

    def decode(reqs):
        at.append([(r.rid, len(r.prompt) + len(r.generated) - 1) for r in reqs])
        return dec(reqs)

    def prefill(req, chunk):
        at.append([(req.rid, req.prefill_pos + chunk - 1)])
        return pre(req, chunk)

    eng._greedy_next, eng._decode_batch, eng._prefill_chunk_run = (
        greedy, decode, prefill)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, 256, n) for n in (40, 27, 33, 21)]
    rids = [eng.add_request(p, 10) for p in prompts]
    eng.run_to_completion()
    assert len(got) == 40
    for rid, p in zip(rids, prompts):
        seq = np.concatenate([p, eng.requests[rid].generated[:-1]])
        ref = model_moe.ref_logits(cfg, params, seq)
        for (r, pos), row in got.items():
            if r == rid:
                # float32 on both sides, summed in other orders (blocked
                # softmax, grouped matmuls): 1.6e-6 of the largest logit
                # measured; 2e-5 leaves room for other seeds
                scale = np.abs(ref[pos]).max()
                np.testing.assert_allclose(row, ref[pos], atol=2e-5 * scale)
    s = eng.stats
    assert s.expert_rows == sum(len(p) + 9 for p in prompts) * 8 * 4
    # 4 layers a call; 64 experts each: a 128-row prefill chunk hits most
    assert s.experts_hit > 0.5 * 64 * 4 * s.prefill_chunks


def run(cell, seed=2**32 + 11, control=False):
    return execute(cell, seed, 0.5, False, control=control, peak=PEAK,
                   t_process=0.0)


def test_sound_run_is_correct_and_the_control_is_not():
    cell = tiny_cell()
    out = run(cell)
    assert out["correct"], out["checks"]
    assert {"logit_gap", "kv_rel_err"} <= set(out["checks"])
    assert {"output_tok_s", "itl_p95_ms", "setup_s"} <= set(out["metrics"])
    e = out["extra"]
    assert e["expert_rows"] > 0 and 0 < e["experts_hit"] <= e["expert_rows"]
    assert 0 <= e["routing_near_ties"] < e["routing_rows"]
    ctl = run(cell, control=True)
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["kv_rel_err"]["value"] > LIMITS["kv_rel_err"]
    assert ctl["extra"]["program_kv_rel_err"] <= LIMITS["kv_rel_err"]


def break_engine(monkeypatch, fault):
    from repro.models import moe as moe_mod
    from repro.models.layers import RunPolicy
    from repro.serve import engine as engine_mod

    if fault == "drop":  # capacity-bounded experts: overflow rows dropped
        def dropping(cfg, p, x):
            y, _ = moe_mod.moe_apply(cfg, p, x,
                                     RunPolicy(moe_capacity_factor=0.5))
            return y, jnp.int32(0)

        monkeypatch.setattr(moe_mod, "moe_apply_dropless", dropping)
        return
    build = serve_moe.build_engine

    def broken(cfg, params):
        eng = build(cfg, params)
        if fault == "no_window":  # sliding layers attend every key
            eng.window = {k: 0 for k in eng.window}
            eng._prefill_rest = {k: engine_mod._program(
                "prefill_layer_rest", engine_mod._prefill_layer_rest, eng.cfg,
                eng.layout, eng.policy, 0) for k in eng.window}
        else:  # full layers without YaRN
            eng._qkv = {k: eng._qkv["local"] for k in eng._qkv}
        return eng

    monkeypatch.setattr(serve_moe, "build_engine", broken)


@pytest.mark.parametrize("fault", ["drop", "no_window", "no_yarn"])
def test_a_broken_block_is_not_correct(monkeypatch, fault):
    break_engine(monkeypatch, fault)
    out = run(tiny_cell())
    assert not out["correct"], out["checks"]


def test_param_tree_and_published_sizes():
    from repro.models import init_params_specs

    cfg = load()
    arch = model_moe.arch_config(cfg)
    assert arch.layer_kinds() == ["local"] * 3 + ["attention"] + [
        "local"] * 3 + ["attention"] + ["local"] * 3 + ["attention"]
    tree = init_params_specs(arch)
    leaves = {jax.tree_util.keystr(k): v.shape for k, v in
              jax.tree_util.tree_flatten_with_path(tree)[0]}
    mine = {jax.tree_util.keystr(k): v[0] for k, v in
            jax.tree_util.tree_flatten_with_path(
                model_moe.leaf_specs(cfg), is_leaf=model_moe._is_spec)[0]}
    assert mine == leaves  # the served tree, leaf for leaf
    assert model_moe.param_count(cfg) == flops_moe.param_count(cfg) \
        == arch.param_count() == 5_465_956_608
    whole = dict(cfg, num_hidden_layers=cfg["published"]["num_hidden_layers"])
    assert flops_moe.param_count(whole) == 12_149_915_904
    assert len(cfg["layer_types"]) == whole["num_hidden_layers"]


def test_reference_yarn_agrees_with_the_program():
    """Two writings of transformers' YaRN: the reference's and the
    program's."""
    from repro.models.layers import yarn_inv_freq

    cfg = load()
    arch = model_moe.arch_config(cfg)
    ref, ref_scale = model_moe.yarn_frequencies(
        cfg["rope_parameters"]["full_attention"], 128,
        cfg["max_position_embeddings"])
    prog, scale = yarn_inv_freq(arch.global_yarn, 128, arch.rope_theta)
    np.testing.assert_allclose(ref, prog, rtol=1e-6)
    assert ref_scale == scale


SMALL = {"num_hidden_layers": 4, "hidden_size": 8, "moe_intermediate_size": 4,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
         "vocab_size": 10, "num_experts": 6, "num_experts_per_tok": 2,
         "sliding_window": 3, "tie_word_embeddings": False,
         "layer_types": ["sliding_attention"] * 3 + ["full_attention"]}


def test_counts_by_hand():
    c = SMALL
    # per token and layer: q, o 8*4*2 each; k, v 8*2*2 each (192 weights);
    # router 8*6; 2 experts of 3*8*4
    assert flops_moe.layer_matmul_params(c) == 64 + 64 + 32 + 32 + 48 + 192
    # keys attended at context 5: 3 in each sliding layer, 5 in the full one
    assert flops_moe.attention_flops(c, 5) == 4 * 4 * 2 * (3 * 3 + 5)
    # prefill positions 2..5 (contexts 3..6): sliding 3+3+3+3, full 3+4+5+6
    assert flops_moe.prefill_flops(c, 2, 6, False) == (
        2 * 4 * 432 * 4 + 4 * 4 * 2 * (3 * 12 + 18))
    assert flops_moe.prefill_flops(c, 0, 2, True) == (
        2 * 4 * 432 * 2 + 4 * 4 * 2 * (3 * 3 + 3) + 2 * 8 * 10)
    assert flops_moe.decode_flops(c, [5, 1]) == (
        2 * (2 * 4 * 432 + 2 * 8 * 10) + 4 * 4 * 2 * (14 + 4))
    # 10 rows that hit 3 experts: 2*96 FLOPs a row; 3 experts' weights and
    # the rows in and out, in bf16
    assert flops_moe.expert_ffn_cost(c, 10, 3, 2) == (
        10 * 2 * 96, (3 * 96 + 2 * 10 * 8) * 2)
    f, b = flops_moe.windowed_attention_cost(c, [5], 2, 4)
    assert f == 4 * 4 * 2 * 14
    # keys and values attended in float32, q read and out written a layer
    assert b == (2 * 2 * 2 * 14) * 4 + 4 * (2 * 4 * 2 * 2)
    assert flops_moe.param_count(c) == 2 * 10 * 8 + 4 * (
        192 + 16 + 48 + 6 * 96) + 8
    # the dense counts agree where the two share a term
    assert flops.head_flops(c) == 2 * 8 * 10
