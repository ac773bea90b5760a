"""A whole serve run on the CPU at a tiny size, past the look for a chip:
sound, it is correct; with the timed path broken underneath, or with the
float8 control's readings in the program's place, it is not."""
import json

import numpy as np
import pytest

from bench import serve, spec
from bench.run import execute

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the widest logit gap and KV error a sound tiny run may show
LIMITS = {"logit_gap": 0.05, "kv_rel_err": 0.05, "short_requests": 0,
          "stuck_requests": 0, "unfinished": 0}


def tiny_cell(config):
    cfg = json.loads((spec.BENCH / "configs" / f"{config}.json").read_text())
    cfg.update(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=256)
    cfg["engine"] = dict(max_seqs=4, max_len=64, page_size=16,
                         memory_model="TPU_V5E")
    # one prompt length and one answer length: few shapes to compile
    mix = {"kind": "closed_loop", "clients": 4, "requests": 1000,
           "lengths_seed": 3, "warm_steps": 4, "prompt_granule": 8,
           "check_requests": 3, "kv_requests": 2, "grace_s": 2.0,
           "prompt": {"median": 8, "sigma": 0.0, "min": 8, "max": 8},
           "output": {"median": 16, "sigma": 0.0, "min": 16, "max": 16}}
    bm = spec.load_benchmark()
    return spec.Cell("tiny", 1, cfg, mix, bm["end_to_end"],
                     [m for m in bm["per_layer"] if m["source"] != "device_trace"],
                     LIMITS)


def run(cell, seed=2**32 + 11, control=False):
    return execute(cell, seed, 0.5, False, control=control, peak=PEAK,
                   t_process=0.0)


def break_engine(monkeypatch, fault):
    if fault == "stall":  # decoding stops once set-up is done
        warm = serve.warm_shapes

        def warm_then_stall(eng, sched, seed):
            warm(eng, sched, seed)
            eng._decode_batch = lambda reqs: None

        monkeypatch.setattr(serve, "warm_shapes", warm_then_stall)
        return
    build = serve.build_engine

    def broken(cfg, params):
        eng = build(cfg, params)
        if fault == "token":  # a token altered where it is produced
            nxt = eng._greedy_next
            eng._greedy_next = lambda p, x: (nxt(p, x) + 1) % cfg["vocab_size"]
        else:  # decode KV left as it was: all of the batch, or half of it
            write = eng.cache.write_token

            def write_token(sids, layer, k, v, pos):
                if fault == "half_kv" and len(sids) > 1:
                    h = len(sids) // 2
                    write(sids[:h], layer, k[:h], v[:h], pos[:h])

            eng.cache.write_token = write_token
        return eng

    monkeypatch.setattr(serve, "build_engine", broken)


@pytest.mark.parametrize("config", ["yi-6b", "starcoder2-7b-d22"])
def test_sound_run_is_correct_and_the_control_is_not(config):
    cell = tiny_cell(config)
    out = run(cell)
    assert out["correct"], out["checks"]
    assert {"logit_gap", "kv_rel_err"} <= set(out["checks"])
    m = out["metrics"]
    assert {"output_tok_s", "itl_p95_ms", "ttft_p50_ms", "setup_s"} <= set(m)
    assert out["attempted"] > 0 and list(out)[-1] == "checks"
    ctl = run(cell, control=True)
    assert not ctl["correct"], ctl["checks"]
    assert ctl["checks"]["kv_rel_err"]["value"] > LIMITS["kv_rel_err"]
    # the same run still reads the program, within the limits
    assert ctl["extra"]["program_kv_rel_err"] <= LIMITS["kv_rel_err"]
    assert ctl["extra"]["program_logit_gap"] <= LIMITS["logit_gap"]


@pytest.mark.parametrize("fault", ["token", "kv", "half_kv", "stall"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    break_engine(monkeypatch, fault)
    out = run(tiny_cell("yi-6b"))
    assert not out["correct"], out["checks"]
    assert all(np.isfinite(c["value"]) for c in out["checks"].values())
