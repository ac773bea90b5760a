"""The serve engine's folded layer programs (serve/engine.py, "Programs"):
logits equal to the model's own forward pass, 3L+2 programs a decode batch
and 3L+1 a prefill chunk, and the engine attributes the programs are
composed from still steering what is served."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import RunPolicy, forward, init_params
from repro.models.layers import apply_norm
from repro.models.transformer import logits_out
from repro.serve import ServeEngine

# a dense config with one layer kind, and a MoE one with sliding-window
# and full layers (3:1, a 16-token window)
MODELS = ["yi-6b", "mellum2-12b-a2.5b"]
PROMPTS = [np.arange(2, 42), np.arange(5, 15), np.arange(7, 30)]
NEW = 6


@pytest.fixture(scope="module", params=MODELS)
def model(request):
    cfg = get_config(request.param).reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def engine(model):
    cfg, params = model
    # 16-token chunks: the 40-token prompt takes three, past the window
    return ServeEngine(cfg, params, max_seqs=4, max_len=96, page_size=16,
                       prefill_chunk=16)


def serve(eng, prompts=PROMPTS):
    rids = [eng.add_request(p, NEW) for p in prompts]
    out = eng.run_to_completion()
    return [out[r] for r in rids]


def test_served_logits_match_the_forward_pass(model):
    """Every sampled position's logits against the full forward pass over
    the prompt and the tokens served before it."""
    cfg, params = model
    eng = engine(model)
    got, at = {}, []
    nxt, dec, pre = eng._greedy_next, eng._decode_batch, eng._prefill_chunk_run

    def greedy(p, x):
        h = apply_norm(cfg.norm, x[:, -1:], p["final_norm"])
        rows = np.asarray(logits_out(cfg, p, h, eng.policy)[:, -1])
        got.update(zip(at[-1], rows))
        return nxt(p, x)

    def decode(reqs):
        at.append([(r.rid, len(r.prompt) + len(r.generated) - 1)
                   for r in reqs])
        return dec(reqs)

    def prefill(req, chunk):
        at.append([(req.rid, req.prefill_pos + chunk - 1)])
        return pre(req, chunk)

    eng._greedy_next, eng._decode_batch, eng._prefill_chunk_run = (
        greedy, decode, prefill)
    served = serve(eng)
    assert len(got) == len(PROMPTS) * NEW
    # no capacity drops: the engine's experts are dropless
    pol = RunPolicy(moe_capacity_factor=1e9)
    for rid, (prompt, gen) in enumerate(zip(PROMPTS, served)):
        seq = np.concatenate([prompt, gen[:-1]]).astype(np.int32)[None]
        ref = np.asarray(forward(cfg, params, seq, pol)[0][0])
        for (r, pos), row in got.items():
            if r == rid:
                np.testing.assert_allclose(row, ref[pos], atol=2e-3,
                                           rtol=1e-3)


def test_programs_a_step(model):
    """One 40-token request in 16-token chunks: two chunks that do not
    sample, the last one that does with the first decode batch in the same
    step, then decode batches alone."""
    cfg, _ = model
    L = cfg.num_layers
    eng = engine(model)
    eng.add_request(PROMPTS[0], NEW)
    seen = []
    while True:
        s0 = eng.stats
        p0, c0, d0 = s0.programs, s0.prefill_chunks, s0.decode_batches
        more = eng.step()
        s = eng.stats
        seen.append((s.programs - p0, s.prefill_chunks - c0,
                     s.decode_batches - d0))
        if not more:
            break
    assert seen == ([(3 * L + 1, 1, 0)] * 2 + [(6 * L + 4, 1, 1)]
                    + [(3 * L + 2, 0, 1)] * (NEW - 2))


def test_dense_programs_thread_no_expert_count(model):
    cfg, _ = model
    eng = engine(model)
    serve(eng, PROMPTS[1:2])
    assert (eng._experts_hit is None) == (not cfg.is_moe)
    assert (eng.stats.experts_hit > 0) == cfg.is_moe


@pytest.fixture(scope="module")
def moe_served():
    cfg = get_config("mellum2-12b-a2.5b").reduced()
    m = cfg, init_params(cfg, jax.random.PRNGKey(0))
    return m, serve(engine(m), PROMPTS[:1])


def patch(eng, seam):
    if seam == "qkv":  # every layer's V negated
        def neg_v(f):
            def qkv(p, x, positions):
                q, k, v = f(p, x, positions)
                return q, k, -v
            return qkv

        eng._qkv = {k: neg_v(f) for k, f in eng._qkv.items()}
    elif seam == "window":  # the decode kernel attends the last 4 keys
        # (the prompt's 40 pass every layer's window)
        eng.window = {k: 4 for k in eng.window}
    elif seam == "write_token":  # decode KV left as it was
        eng.cache.write_token = lambda sids, layer, k, v, pos: None
    else:  # a token altered where it is produced
        nxt, V = eng._greedy_next, eng.cfg.vocab_size
        eng._greedy_next = lambda p, x: (nxt(p, x) + 1) % V


@pytest.mark.parametrize("seam", ["qkv", "window", "write_token",
                                  "greedy_next"])
def test_patched_seams_steer_what_is_served(moe_served, seam):
    """The folded programs read the engine's attributes when traced, and
    the step calls the pool's write and the sampler by attribute: patching
    any of them on a built engine changes the tokens served."""
    model, sound = moe_served
    eng = engine(model)
    patch(eng, seam)
    assert serve(eng, PROMPTS[:1]) != sound
