"""The device's idle time split by the program's host spans, the programs
per step, and the split of a traced run: the sweep against a brute-force
attribution on random intervals, a trace recorded on a TPU v5e (which holds
no program spans), spans read from a trace taken here, and hand-made runs."""
import glob
import os
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from bench import program_spans as ps
from bench import trace_reduce as tr
from bench.spec import metric_module
from bench.split_run import split

CHIP_TRACE = Path(__file__).parent / "data" / "v5e_paged_attention.xplane.pb"
NAMES = ["serve.step", "serve.admit", "serve.prefill", "serve.decode",
         "serve.qkv", "serve.sample", "serve.kv_write", "serve.kv_gather",
         "umem.launch", "umem.sync"]


def brute_force(idle, spans):
    """Cut the line at every edge; give each piece to the span opened last
    (of two, the shorter) among those open over its midpoint."""
    spans = [x for x in spans if x[2] > x[1]]
    cuts = sorted({t for iv in idle for t in iv}
                  | {t for _, s, e in spans for t in (s, e)})
    acc = dict.fromkeys(ps.BUCKETS, 0.0)
    for a, b in zip(cuts, cuts[1:]):
        m = (a + b) / 2
        if not any(lo <= m < hi for lo, hi in idle):
            continue
        over = [x for x in spans if x[1] <= m < x[2]]
        if not any(n == "serve.step" for n, _, _ in over):
            acc["outside_step"] += b - a
        else:
            inner = max(over, key=lambda x: (x[1], -x[2]))
            acc[ps.bucket(inner[0])] += b - a
    return acc


def random_case(rng):
    window = (0.0, 100.0)
    busy = tr.union([tuple(sorted(rng.uniform(0, 100, 2)))
                     for _ in range(rng.integers(0, 40))])
    spans = []
    for _ in range(rng.integers(0, 8)):  # steps with spans nested inside
        a, b = sorted(rng.uniform(0, 100, 2))
        spans.append(("serve.step", a, b))
        for _ in range(rng.integers(0, 6)):
            c, d = sorted(rng.uniform(a, b, 2))
            spans.append((NAMES[rng.integers(1, len(NAMES))], c, d))
    for _ in range(rng.integers(0, 10)):  # and spans that nest in nothing
        c, d = sorted(rng.uniform(0, 100, 2))
        spans.append((NAMES[rng.integers(0, len(NAMES))], c, d))
    spans.append(("serve.qkv", 50.0, 50.0))  # empty spans count for nothing
    return window, busy, spans


@pytest.mark.parametrize("seed", range(6))
def test_sweep_equals_brute_force(seed):
    rng = np.random.default_rng(seed)
    for _ in range(20):
        window, busy, spans = random_case(rng)
        idle = tr.subtract([window], busy)
        got = ps.idle_split(idle, spans)
        assert got == pytest.approx(brute_force(idle, spans), abs=1e-9)
        s = tr.Summary([tr.Device("d", ops=[("op", a, b) for a, b in busy])],
                       spans=[], window=window)
        shares = ps.idle_in(s, spans)
        assert set(shares) == {f"idle_in.{b}" for b in ps.BUCKETS}
        assert sum(shares.values()) == pytest.approx(
            100.0 * (1 - s.busy_s() / s.window_s))


def test_buckets():
    assert [ps.bucket(n) for n in NAMES] == [
        "scheduler", "scheduler", "dispatch", "dispatch", "dispatch",
        "dispatch", "kv_pool", "kv_pool", "umem", "umem"]
    # nested: step > decode > kv_write; idle under each piece goes to the
    # innermost span open over it
    spans = [("serve.step", 0, 10), ("serve.decode", 1, 9),
             ("serve.kv_write", 2, 3), ("umem.launch_batch", 5, 6)]
    got = ps.idle_split([(-1, 11)], spans)
    assert got == pytest.approx({"dispatch": 6.0, "kv_pool": 1.0,
                                 "umem": 1.0, "scheduler": 2.0,
                                 "outside_step": 2.0})


def test_a_trace_recorded_on_the_chip_has_no_program_spans():
    s = tr.reduce_file(str(CHIP_TRACE))
    s.window = next(x for x in s.spans if x[0] == "window")[1:]
    spans = ps.read_spans(str(CHIP_TRACE))
    assert spans == []
    shares = ps.idle_in(s, spans)
    assert shares["idle_in.outside_step"] == pytest.approx(
        100.0 * s.idle_share())
    assert all(shares[f"idle_in.{b}"] == 0.0 for b in ps.BUCKETS[:-1])


def test_spans_read_from_a_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.step"):
        with TraceAnnotation("serve.step", step=0):
            with TraceAnnotation("serve.prefill", rid=3, start=0, end=8):
                jax.block_until_ready(jax.numpy.ones(4) + 1)
            with TraceAnnotation("umem.sync"):
                pass
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = sorted(ps.read_spans(path), key=lambda x: x[1])
    assert [n for n, _, _ in spans] == ["serve.step", "serve.prefill",
                                        "umem.sync"]
    step = spans[0]
    assert all(step[1] <= s and e <= step[2] for _, s, e in spans)


def hand_made_run():
    """A traced window of 10 s and 4 steps on one device: 12 programs, 2
    of them starting outside the window."""
    mods = [("jit_layer_qkv", t, t + 0.1) for t in np.arange(0.5, 10, 1.0)]
    mods += [("jit_embed", -1.0, -0.5), ("jit_embed", 10.5, 11.0)]
    mods += [("jit_greedy_next", 2.0, 2.1), ("jit_greedy_next", 4.0, 4.1)]
    dev = tr.Device("/device:TPU:0", ops=[(n, s, e) for n, s, e in mods],
                    modules=mods)
    summary = tr.Summary([dev], spans=[], window=(0.0, 10.0))
    steps = [SimpleNamespace(t0=t, t1=t + 2.5, tokens=2) for t in
             (100.0, 102.5, 105.0, 107.5)]
    return SimpleNamespace(trace=summary, steps=steps,
                           window=(100.0, 110.0), window_s=10.0)


def test_programs_per_step_reads_a_hand_made_run():
    read = metric_module("programs_per_step").read
    run = hand_made_run()
    assert read(run) == pytest.approx(12 / 4)
    assert read(SimpleNamespace(trace=None, steps=run.steps)) is None
    assert read(SimpleNamespace(trace=run.trace, steps=[])) is None


def test_split_of_a_hand_made_run():
    run = hand_made_run()
    req = SimpleNamespace
    requests = {
        0: req(arrival_wall=90.0, prefill_wall=95.0, first_token_wall=101.0),
        1: req(arrival_wall=99.0, prefill_wall=104.0, first_token_wall=104.5),
        2: req(arrival_wall=100.0, prefill_wall=109.0,
               first_token_wall=111.0),  # after the window
        3: req(arrival_wall=108.0, prefill_wall=None, first_token_wall=None),
    }
    spans = [("serve.step", 0.0, 10.0), ("serve.decode", 1.0, 9.0)]
    out = split({"rec": run, "spans": spans, "requests": requests,
                 "reduce_s": 2.0, "metrics_s": 1.0, "read_s": 0.5})
    assert out["first_tokens"] == 2
    assert out["ttft_queue_p50_ms"] == pytest.approx(5000.0)
    assert out["ttft_prefill_p50_ms"] == pytest.approx(3250.0)
    assert out["output_tok_s"] == pytest.approx(0.8)
    assert out["reduce_s"]["harness"] == 3.0
    assert out["reduce_s"]["split"] >= 0.5
    idle = {k: v for k, v in out.items() if k.startswith("idle_in.")}
    assert sum(idle.values()) == pytest.approx(out["idle_share"])
    assert idle["idle_in.dispatch"] > idle["idle_in.scheduler"] > 0
    assert idle["idle_in.outside_step"] == idle["idle_in.umem"] == 0
