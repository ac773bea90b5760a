"""The serve engine's host spans, program names and wall-clock stamps: a
tiny engine served under the profiler, its trace read back."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import UnifiedMemory
from repro.models import init_params
from repro.serve import ServeEngine
from repro.serve import paged

PROMPTS = [np.arange(2, 42), np.arange(5, 15), np.arange(7, 30)]
NEW = 5


@pytest.fixture(scope="module")
def model():
    cfg = get_config("yi-6b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def engine(model, um=None):
    cfg, params = model
    # 16-token chunks: the 40-token prompt takes three
    return ServeEngine(cfg, params, max_seqs=4, max_len=96, page_size=16,
                       prefill_chunk=16, um=um)


def traced_spans(eng, tmp_path):
    """Serve PROMPTS to completion under the profiler; the ``serve.*`` and
    ``umem.*`` host events as (name, start_ns, end_ns, args)."""
    rids = [eng.add_request(p, NEW) for p in PROMPTS]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_to_completion()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
              dict(ev.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(("serve.", "umem."))]
    return rids, spans


def inside(outer, inner):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("with_um", [False, True], ids=["no_um", "um"])
def test_spans_nest_in_steps_and_count_layers(model, tmp_path, with_um):
    eng = engine(model, UnifiedMemory() if with_um else None)
    rids, spans = traced_spans(eng, tmp_path)
    steps = [s for s in spans if s[0] == "serve.step"]
    assert [s[3]["step"] for s in steps] == list(range(len(steps)))
    for s in spans:
        assert any(inside(st, s) for st in steps), s
    assert any(s[0].startswith("umem.") for s in spans) == with_um
    L = model[0].num_layers
    prefills = [s for s in spans if s[0] == "serve.prefill"]
    decodes = [s for s in spans if s[0] == "serve.decode"]
    assert len(prefills) == eng.stats.prefill_chunks
    assert len(decodes) == eng.stats.decode_batches
    for outer in prefills + decodes:
        names = [s[0] for s in spans if inside(outer, s) and s is not outer]
        for per_layer in ("serve.kv_write", "serve.layer_rest"):
            assert names.count(per_layer) == L, (outer, per_layer)
        # one program embeds and projects layer 0's QKV; every later
        # layer's QKV runs inside the layer before's serve.layer_rest
        (embed,) = [s for s in spans if s[0] == "serve.embed"
                    and inside(outer, s)]
        (qkv,) = [s for s in spans if s[0] == "serve.qkv"
                  and inside(outer, s)]
        assert inside(embed, qkv)
        if outer[0] == "serve.prefill":
            assert names.count("serve.kv_gather") == L
        else:
            assert names.count("serve.attention") == L
            assert names.count("serve.sample") == 1
            assert names.count("serve.kv_view") == 1
            assert outer[3]["batch"] >= 1
    # every prefill chunk names its request, and each request's chunks
    # tile its prompt
    for rid, prompt in zip(rids, PROMPTS):
        chunks = sorted((s[3]["start"], s[3]["end"]) for s in prefills
                        if s[3]["rid"] == rid)
        assert chunks[0][0] == 0 and chunks[-1][1] == len(prompt)
        assert all(a[1] == b[0] for a, b in zip(chunks, chunks[1:]))
    assert {s[3]["rid"] for s in prefills} == set(rids)


def test_programs_have_names_of_their_own(model):
    cfg, params = model
    eng = engine(model)
    x = jax.numpy.zeros((1, 4, cfg.d_model), jax.numpy.float32)
    pos = np.arange(4, dtype=np.int32)
    toks = np.zeros((1, 4), np.int32)
    p = params["layers"][0]
    qkv, prefill_rest = eng._qkv["attention"], eng._prefill_rest["attention"]
    q, k, v = qkv(p, x, pos)
    kpos = np.arange(4, dtype=np.int32)
    o = jax.numpy.zeros((1, eng.layout.n_q_eff, cfg.head_dim))
    pools = eng.cache.k_pools[0], eng.cache.v_pools[0]
    pids, slots = np.ones(4, np.int32), pos
    dpos = pos[:1, None]
    lowered = {
        "embed": eng._embed.lower(params, toks, pos),
        "layer_qkv": qkv.lower(p, x, pos),
        "prefill_layer_rest": prefill_rest.lower(
            p, x, q, k[0], v[0], pos, kpos, None),
        "layer_rest": eng._decode_rest.lower(p, x[:, :1], o[:, None], None),
        "greedy_next": eng._greedy_next.lower(params, x),
        "kv_write": paged.kv_write.lower(*pools, pids, slots, k, v),
        "kv_gather": paged.kv_gather.lower(*pools, pids, slots),
        # the programs the step dispatches, folded from those above
        "embed_qkv": eng._decode_head.lower(params, toks[:, :1], dpos),
        "layer_rest_qkv": eng._decode_fold["attention"].lower(
            p, x[:, :1], o, None, p, dpos),
        "prefill_layer_rest_qkv": eng._prefill_fold[
            "attention", "attention"].lower(
            p, x, q, k[0], v[0], pos, kpos, None, p),
    }
    assert eng._decode_fold[None].lower(
        p, x[:, :1], o, None, None, dpos).as_text().startswith(
        "module @jit_layer_rest ")
    assert eng._prefill_fold["attention", None].lower(
        p, x, q, k[0], v[0], pos, kpos, None, None).as_text().startswith(
        "module @jit_prefill_layer_rest ")
    assert eng._prefill_head.lower(params, toks, pos).as_text().startswith(
        "module @jit_embed_qkv ")
    for name, low in lowered.items():
        head = low.as_text().splitlines()[0]
        assert head.startswith(f"module @jit_{name} "), head
        assert "_unknown" not in head and "_lambda" not in head
        assert "paged_attention" not in name


def test_wall_stamps_order_and_modeled_stamps_stay(model):
    eng = engine(model, UnifiedMemory())
    rids = [eng.add_request(p, NEW) for p in PROMPTS]
    eng.run_to_completion()
    for rid in rids:
        r = eng.requests[rid]
        assert 0 < r.arrival_wall <= r.prefill_wall <= r.first_token_wall
        # the modeled clock still stamps arrival, admission, first token
        assert r.arrival_time <= r.admit_time <= r.first_token_time \
            <= r.finish_time
    # FIFO over a shared prefill budget: a later request starts its prefill
    # no earlier than an earlier one
    starts = [eng.requests[rid].prefill_wall for rid in rids]
    assert starts == sorted(starts)


def test_attention_spans_carry_each_layers_window(tmp_path):
    """Sliding-window and full layers side by side: each decode layer's
    ``serve.attention`` span names the window its kernel ran with."""
    cfg = get_config("mellum2-12b-a2.5b").reduced()
    eng = engine((cfg, init_params(cfg, jax.random.PRNGKey(0))))
    _, spans = traced_spans(eng, tmp_path)
    windows = [s[3]["window"] for s in sorted(spans, key=lambda s: s[1])
               if s[0] == "serve.attention"]
    per_step = [cfg.local_window if k == "local" else 0
                for k in cfg.layer_kinds()]
    assert windows == per_step * eng.stats.decode_batches
    assert per_step == [16, 16, 16, 0]
