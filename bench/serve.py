"""The serve runner: a closed loop of clients against ``ServeEngine``.

A run has three phases on one engine:

1. shapes: a few probe requests, served to completion, compile (or load
   from the persistent cache) every program the mix can run: one request
   of the mix's longest prompt covers every prefill shape (128-token chunks
   over every prefix length up to it), and ``clients - 1`` one-granule
   prompts alive together cover every decode batch size.
2. the closed loop starts from the emptied engine; after ``warm_steps``
   steps the window opens and runs ``--seconds``. Programs compiled or
   loaded inside it are counted (``compiles_in_window``).
3. at the close, the KV of a sample of the requests still in flight is
   read from the pools; the loop then stops sending and, where fewer than
   ``check_requests`` requests finished in the window, steps on for up to
   ``grace_s`` more seconds. The engine is freed, and a seeded sample of
   the finished requests and the in-flight ones is compared with the
   float32 reference (``correctness``).

Stamps are wall-clock (``time.perf_counter``), taken when ``step()``
returns; a client's request is sent when its previous one finished.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from bench import model
from bench.clock import compile_clock
from bench.traffic import make_schedule

UMEM_CALLS = ("launch", "launch_batch", "sync", "prefetch_async", "demote")


@dataclass
class Step:
    t0: float
    t1: float
    chunks: List[Tuple[int, int, bool]]  # prefill (start, end, ends prompt)
    contexts: List[int]                  # keys each decoded token attended
    tokens: int                          # output tokens this step produced
    programs: int                        # compiled or loaded in the step


@dataclass
class Req:
    sent: float
    prompt_len: int
    max_new: int
    token_times: List[float] = field(default_factory=list)
    finished: Optional[float] = None


class Loop:
    """The closed loop: steps the engine, sends each client's next request
    when its last one finishes, and stamps every request's tokens."""

    def __init__(self, eng, sched, clock):
        self.eng, self.sched, self.clock = eng, sched, clock
        self.steps: List[Step] = []
        self.reqs: Dict[int, Req] = {}  # engine rid -> Req
        self.next_index = 0
        self.started = False
        self.sending = True  # False once the window has closed

    def _send(self, t: float) -> None:
        prompt, max_new = self.sched.request(self.next_index)
        self.next_index += 1
        rid = self.eng.add_request(prompt, max_new)
        self.reqs[rid] = Req(t, len(prompt), max_new)

    def step(self) -> Step:
        eng = self.eng
        if not self.started:
            self.started = True
            t = time.perf_counter()
            for _ in range(self.sched.clients):
                self._send(t)
        live = {rid: (r.prefill_pos, len(r.generated))
                for rid, r in eng.requests.items() if not r.done}
        n0 = self.clock.programs
        t0 = time.perf_counter()
        eng.step()
        t1 = time.perf_counter()
        chunks, contexts, tokens = [], [], 0
        for rid, (pos0, gen0) in live.items():
            r = eng.requests[rid]
            if r.prefill_pos > pos0:
                chunks.append((pos0, r.prefill_pos,
                               r.prefill_pos == len(r.prompt)))
            new = len(r.generated) - gen0
            if new:
                tokens += new
                self.reqs[rid].token_times += [t1] * new
                decoded = new - (1 if gen0 == 0 else 0)
                if decoded:
                    contexts.append(len(r.prompt) + len(r.generated) - 1)
            if r.done:
                self.reqs[rid].finished = t1
                if self.sending:
                    self._send(t1)
        s = Step(t0, t1, chunks, contexts, tokens, self.clock.programs - n0)
        self.steps.append(s)
        return s


def drain() -> None:
    """Wait for all work queued on the device: a new op runs after every
    earlier one."""
    jax.block_until_ready(jax.numpy.zeros(()) + 1)


def warm_shapes(eng, sched, seed: int) -> None:
    """Serve the probe requests to completion (see the module doc)."""
    rng = np.random.default_rng([seed, 1 << 40])
    c = sched.clients
    longest, granule = int(sched.prompt_len.max()), sched.granule
    # every request stays alive until the last one has its first token
    max_new = min(longest // granule + c + 1, eng.max_len - 1 - longest)
    for n in [longest] + [granule] * (c - 1):
        eng.add_request(rng.integers(2, sched.vocab_size, n, dtype=np.int32),
                        max_new)
    while eng.step():
        pass


def time_umem(um, sink: Dict[str, float]) -> None:
    """Wrap the memory model's calls on this instance so the seconds the
    engine spends in them add up in ``sink['s']``."""
    for name in UMEM_CALLS:
        fn = getattr(um, name)

        def timed(*a, _fn=fn, **k):
            t = time.perf_counter()
            try:
                return _fn(*a, **k)
            finally:
                sink["s"] += time.perf_counter() - t

        setattr(um, name, timed)


def annotate(eng) -> None:
    """Host spans (``bench.<call>``) around the engine's phases and the
    memory model's calls, for the traced run's idle-gap attribution."""
    from jax.profiler import TraceAnnotation

    targets = [(eng, n) for n in ("step", "_admit", "_prefill_step",
                                  "_decode_batch")]
    if eng.um is not None:
        targets += [(eng.um, n) for n in UMEM_CALLS]
    for obj, name in targets:
        fn = getattr(obj, name, None)
        if fn is None:
            continue

        def spanned(*a, _fn=fn, _n="bench." + name.lstrip("_"), **k):
            with TraceAnnotation(_n):
                return _fn(*a, **k)

        setattr(obj, name, spanned)


def build_engine(cfg: Dict[str, Any], params):
    from repro.core import UnifiedMemory, hardware
    from repro.serve import ServeEngine

    e = dict(cfg["engine"])
    hw = e.pop("memory_model")
    um = UnifiedMemory(hw=getattr(hardware, hw)) if hw else None
    return ServeEngine(model.arch_config(cfg), params, um=um, **e)


@dataclass
class ServeRun:
    """What a serve run leaves for the metric readers."""

    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    steps: List[Step]        # the window's steps
    reqs: List[Req]
    window: Tuple[float, float]
    setup_s: float
    warm_programs: int
    umem_s: float
    stats: Dict[str, int]    # EngineStats deltas over the window
    kv_itemsize: int
    q_itemsize: int
    peak: Dict[str, Any]
    trace: Any = None        # bench.trace_reduce.Summary of the window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _stats(eng) -> Dict[str, int]:
    return dict(vars(eng.stats))


def run(cell, seed: int, seconds: float, *, trace: bool, t_process: float,
        peak: Dict[str, Any], limits: Dict[str, float], control: bool = False,
        log=print):
    """Serve ``cell`` and return (ServeRun, checks, extra)."""
    cfg, mix = cell.config, cell.traffic
    clock = compile_clock()
    t = time.perf_counter()
    params = jax.block_until_ready(model.make_params(cfg, seed))
    eng = build_engine(cfg, params)
    log(f"weights and engine: {time.perf_counter() - t:.1f} s")
    umem = {"s": 0.0}
    if eng.um is not None:
        time_umem(eng.um, umem)
    sched = make_schedule(mix, seed, cfg["vocab_size"], cfg["engine"]["max_len"])
    warm_steps = int(mix["warm_steps"])

    t = time.perf_counter()
    warm_shapes(eng, sched, seed)
    log(f"shapes: {len(eng.requests)} probe requests in "
        f"{time.perf_counter() - t:.1f} s")
    loop = Loop(eng, sched, clock)
    while len(loop.steps) < warm_steps:
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
    setup_s = t_w0 - t_process
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
    win_steps = loop.steps[n0:]
    reqs = list(loop.reqs.values())
    kv_itemsize = eng.cache.k_pools[0].dtype.itemsize
    q_itemsize = np.dtype(cfg["dtype"]).itemsize
    run_rec = ServeRun(
        cfg=cfg, mix=mix, steps=win_steps, reqs=reqs, window=(t_w0, t_w1),
        setup_s=setup_s, warm_programs=clock.programs,
        umem_s=umem["s"] - umem0,
        stats={k: stats1[k] - stats0[k] for k in stats1},
        kv_itemsize=kv_itemsize, q_itemsize=q_itemsize, peak=peak,
        trace=summary)
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
    extra["window_steps"] = len(win_steps)
    extra["max_step_ms"] = max((s.t1 - s.t0 for s in win_steps),
                               default=0.0) * 1e3
    return run_rec, checks, extra


def pick(rng, sizes: List[Tuple[int, int]], n: int) -> List[int]:
    """n ids of ``sizes`` (id, size): the largest, then a seeded draw."""
    order = sorted(sizes, key=lambda d: -d[1])
    rest = [order[1:][i] for i in rng.permutation(max(len(order) - 1, 0))]
    return [rid for rid, _ in (order[:1] + rest)[:n]]


def snapshot_kv(eng, rids: List[int]) -> Dict[int, Any]:
    """What the pools hold for each request of ``rids``: its tokens whose KV
    is written, its served tokens, and per layer (K, V), (tokens, Hkv, D)
    each. Whole pages are read through the page table (one shape a cell)."""
    c = eng.cache
    out = {}
    for rid in rids:
        r = eng.requests[rid]
        n = int(c.lengths[r.sid])
        pages = jax.numpy.asarray(c.page_table[r.sid])

        def take(pool):
            a = np.asarray(pool[pages])  # (pages, Hkv, page_size, D)
            a = a.transpose(0, 2, 1, 3).reshape(-1, a.shape[1], a.shape[3])
            return np.ascontiguousarray(a[:n])

        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.generated, np.int32)])[:n]
        out[rid] = (seq, list(r.generated)[:max(0, n - len(r.prompt) + 1)],
                    [(take(k), take(v))
                     for k, v in zip(c.k_pools, c.v_pools)])
    return out


def correctness(cfg, params, served, live, limits, *, control, log,
                unfinished: int, stuck: int, short: int):
    """Compare the finished sample's served tokens, and the in-flight
    sample's served tokens and KV, with the float32 reference. With
    ``control`` the float8 reference's readings are the ones checked; the
    program's are still read, into ``extra``."""
    t = time.perf_counter()
    reads = {False: [], True: []}
    for cut in ([False, True] if control else [False]):
        for prompt, gen in served.values():
            seq = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
            reads[cut].append(model.judge(cfg, params, seq, gen, control=cut))
        for seq, gen, kv in live.values():
            reads[cut].append(model.judge(cfg, params, seq, gen, kv=kv,
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
    extra = {"compared_tokens": int(len(gap)),
             "compared_requests": len(served), "kv_requests": len(live),
             "distinct_served": len({t for _, g in served.values() for t in g}
                                    | {t for _, g, _ in live.values()
                                       for t in g}),
             "margin_p10": float(np.percentile(margin, 10)) if len(margin)
             else None}
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
