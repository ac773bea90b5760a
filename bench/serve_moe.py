"""The serve runner for ``bench/model_moe.py``'s configurations: the closed
loop of ``bench/serve.py`` (its ``Loop``, probe requests, KV snapshot and
spans, by import) against ``ServeEngine`` serving a sparse-expert model with
sliding-window and full attention layers, checked against that module's
float32 reference.

Beside ``bench/serve.py``'s readings, ``extra`` carries the engine's expert
counters over the window (``expert_rows``, ``experts_hit``) and the
reference's routing near ties over the compared requests: (position, layer)
pairs whose k-th and (k+1)-th router scores lie within bf16 rounding, where
the program may route a token to the other expert (``routing_near_ties`` of
``routing_rows``). The reference never copies the program's routing; the
limits on ``logit_gap`` and ``kv_rel_err`` hold with those flips in.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict

import jax
import numpy as np

from bench import model_moe
from bench.clock import compile_clock
from bench.serve import (Loop, ServeRun, annotate, drain, pick, snapshot_kv,
                         time_umem, warm_shapes)
from bench.traffic import make_schedule


def build_engine(cfg: Dict[str, Any], params):
    from repro.core import UnifiedMemory, hardware
    from repro.serve import ServeEngine

    e = dict(cfg["engine"])
    hw = e.pop("memory_model")
    um = UnifiedMemory(hw=getattr(hardware, hw)) if hw else None
    return ServeEngine(model_moe.arch_config(cfg), params, um=um, **e)


def _stats(eng) -> Dict[str, int]:
    return dict(vars(eng.stats))


def run(cell, seed: int, seconds: float, *, trace: bool, t_process: float,
        peak: Dict[str, Any], limits: Dict[str, float], control: bool = False,
        log=print):
    """Serve ``cell`` and return (ServeRun, checks, extra)."""
    cfg, mix = cell.config, cell.traffic
    model_moe.arch_config(cfg)  # a program without the block fails here
    clock = compile_clock()
    t = time.perf_counter()
    params = jax.block_until_ready(model_moe.make_params(cfg, seed))
    eng = build_engine(cfg, params)
    log(f"weights and engine: {time.perf_counter() - t:.1f} s")
    umem = {"s": 0.0}
    if eng.um is not None:
        time_umem(eng.um, umem)
    sched = make_schedule(mix, seed, cfg["vocab_size"], cfg["engine"]["max_len"])

    t = time.perf_counter()
    warm_shapes(eng, sched, seed)
    log(f"shapes: {len(eng.requests)} probe requests in "
        f"{time.perf_counter() - t:.1f} s")
    loop = Loop(eng, sched, clock)
    while len(loop.steps) < int(mix["warm_steps"]):
        loop.step()
    drain()
    log(f"warm-up: {time.perf_counter() - t:.1f} s, {clock.programs} programs "
        f"compiled or loaded ({clock.cache_hits} from the persistent cache)")
    tracer = span = None
    if trace:
        from jax.profiler import TraceAnnotation
        from bench.trace_reduce import Tracer
        annotate(eng)
        tracer, span = Tracer(), TraceAnnotation("bench.window")
        tracer.start()
        span.__enter__()
    stats0, umem0 = _stats(eng), umem["s"]
    t_w0 = time.perf_counter()
    n0 = len(loop.steps)
    while time.perf_counter() - t_w0 < seconds:
        loop.step()
    drain()
    t_w1 = time.perf_counter()
    summary = None
    if tracer:
        span.__exit__(None, None, None)
        summary = tracer.stop()
    stats1 = _stats(eng)
    reqs = list(loop.reqs.values())
    run_rec = ServeRun(
        cfg=cfg, mix=mix, steps=loop.steps[n0:], reqs=reqs, window=(t_w0, t_w1),
        setup_s=t_w0 - t_process, warm_programs=clock.programs,
        umem_s=umem["s"] - umem0,
        stats={k: stats1[k] - stats0[k] for k in stats1},
        kv_itemsize=eng.cache.k_pools[0].dtype.itemsize,
        q_itemsize=np.dtype(cfg["dtype"]).itemsize, peak=peak, trace=summary)
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())

    # after the window: the in-flight sample's KV, the wait for finished
    # requests, then the engine is freed and the reference runs
    rng = np.random.default_rng([seed, 7])
    live = snapshot_kv(eng, pick(rng, [
        (rid, int(eng.cache.lengths[r.sid])) for rid, r in eng.requests.items()
        if not r.done and r.sid >= 0 and eng.cache.lengths[r.sid] > 0],
        int(mix["kv_requests"])))
    stuck = sum(1 for q in reqs if q.token_times and q.token_times[0] < t_w0
                and (q.finished is None or q.finished >= t_w0)
                and not any(t_w0 <= t <= t_w1 for t in q.token_times))
    need = int(mix["check_requests"])
    loop.sending = False
    t_end = t_w1 + float(mix["grace_s"])

    def finished():
        return [(rid, q) for rid, q in loop.reqs.items()
                if q.finished is not None and q.finished >= t_w0]

    while (len(finished()) < need and time.perf_counter() < t_end
           and any(not r.done for r in eng.requests.values())):
        loop.step()
    done = finished()
    chosen = pick(rng, [(rid, q.prompt_len + q.max_new) for rid, q in done],
                  need)
    served = {rid: (np.asarray(eng.requests[rid].prompt),
                    list(eng.requests[rid].generated)) for rid in chosen}
    del eng, loop
    gc.collect()  # the spans' wrappers hold the engine in cycles
    checks, extra = correctness(
        cfg, params, served, live, limits, control=control, log=log,
        unfinished=need - min(need, len(done)), stuck=stuck,
        short=sum(1 for _, q in done if len(q.token_times) != q.max_new))
    extra["memory_peak_bytes"] = mem_peak
    extra["attempted"] = sum(1 for q in reqs if q.sent <= t_w1 and (
        q.finished is None or q.finished >= t_w0))
    extra["window_steps"] = len(run_rec.steps)
    extra["max_step_ms"] = max((s.t1 - s.t0 for s in run_rec.steps),
                               default=0.0) * 1e3
    extra["expert_rows"] = run_rec.stats["expert_rows"]
    extra["experts_hit"] = run_rec.stats["experts_hit"]
    return run_rec, checks, extra


def correctness(cfg, params, served, live, limits, *, control, log,
                unfinished: int, stuck: int, short: int):
    """``bench/serve.py``'s comparison, against this module's reference:
    the finished sample's served tokens, and the in-flight sample's served
    tokens and KV. With ``control`` the float8 reference's readings are the
    ones checked; the program's are still read, into ``extra``."""
    t = time.perf_counter()
    reads = {False: [], True: []}
    for cut in ([False, True] if control else [False]):
        for prompt, gen in served.values():
            seq = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
            reads[cut].append(model_moe.judge(cfg, params, seq, gen,
                                              control=cut))
        for seq, gen, kv in live.values():
            reads[cut].append(model_moe.judge(cfg, params, seq, gen, kv=kv,
                                              control=cut))

    def summary(rs):
        gap = np.concatenate([r["gap"] for r in rs] + [np.zeros(0)])
        kv = np.concatenate([r["kv_err"] for r in rs if "kv_err" in r]
                            + [np.zeros(0)])
        return gap, kv

    gap, kv = summary(reads[False])
    log(f"reference: {len(served)} finished and {len(live)} in-flight "
        f"requests, {len(gap)} served tokens in {time.perf_counter() - t:.1f} s")
    checks = {
        "unfinished": {"value": unfinished, "limit": limits["unfinished"]},
        "stuck_requests": {"value": stuck, "limit": limits["stuck_requests"]},
        "short_requests": {"value": short, "limit": limits["short_requests"]},
    }
    margin = np.concatenate([r["margin"] for r in reads[False]]
                            + [np.zeros(0)])
    seqs = [len(p) + len(g) - 1 for p, g in served.values()] + [
        len(s) for s, _, _ in live.values()]
    extra = {"compared_tokens": int(len(gap)),
             "compared_requests": len(served), "kv_requests": len(live),
             "distinct_served": len({t for _, g in served.values() for t in g}
                                    | {t for _, g, _ in live.values()
                                       for t in g}),
             "margin_p10": float(np.percentile(margin, 10)) if len(margin)
             else None,
             "routing_near_ties": int(sum(r["ties"].sum() for r in reads[False])),
             "routing_rows": int(sum(seqs) * cfg["num_hidden_layers"])}
    if control:
        extra["program_logit_gap"] = float(gap.max()) if len(gap) else None
        extra["program_kv_rel_err"] = float(kv.max()) if len(kv) else None
        extra["program_flipped_share"] = float(np.mean(gap > 0)) if len(gap) \
            else None
        gap, kv = summary(reads[True])
        extra["control_flipped_share"] = float(np.mean(gap > 0)) if len(gap) \
            else None
    if len(gap):
        checks["logit_gap"] = {"value": float(gap.max()),
                               "limit": limits["logit_gap"]}
    if len(kv):
        checks["kv_rel_err"] = {"value": float(kv.max()),
                                "limit": limits["kv_rel_err"]}
    return checks, extra
