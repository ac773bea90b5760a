"""Oversubscription-aware continuous-batching serve engine.

Requests move through the scheduler states

    pending -> prefill -> decoding -> (preempted <-> decoding)* -> done

driven by one ``step()`` per engine iteration:

  1. **Admission control** — preempted sequences resume first (oldest rid
     first), then pending requests are admitted FIFO. Admission consults
     both the KV pool (enough free pages for the whole prompt plus a
     watermark) and, when a :class:`UnifiedMemory` governs the pool, device
     memory pressure: a request is only admitted while
     ``um.device_free()`` covers ``admit_device_fraction`` of its projected
     KV growth (prompt + max_new_tokens). The pressure gate is skipped when
     nothing is running, so the engine always makes progress.
  2. **Chunked prefill** — at most ``prefill_chunk`` prompt tokens are
     prefilled per step (shared FIFO budget), so one long prompt cannot
     stall decode for everyone else. Each chunk attends over the KV already
     in the pool (gathered per layer), which makes chunked and unchunked
     prefill bit-identical.
  3. **Async prefetch** — resumed sequences' pool extents are promoted
     ahead of their decode turn via ``um.prefetch_async`` (cost hides under
     the decode kernel through ``_pending_overlap``).
  4. **Batched decode** — one paged-attention step over every decoding
     sequence. If the pool cannot back the batch's new-token pages, the
     youngest decoding sequences are *preempted* instead of hitting a
     ``page pool exhausted`` assert: their KV is demoted host-side
     (``um.demote`` + ``PagedKVCache.swap_out``) and scattered back on
     resume, after which the access-counter path re-promotes the hot pages.

Decode uses the paged_attention Pallas kernel over the umem-governed page
pool, which may be allocated larger than device capacity (``num_pages``):
overflow pages live host-side under the system policy and decode reads
them remotely — the paper's §7 graceful oversubscription, applied to
serving. Attention-arch only (recurrent archs serve via the dense decode
path in models/transformer.py — their state is O(1) in sequence length).

**Layer kinds.** A config's layers are global attention (``attention``) or
sliding-window attention (``local``, ``cfg.local_window``), in any pattern.
Each kind has its own programs, built once: the kind fixes the window (in
the prefill chunk's causal bias and as the paged kernel's static
``window``) and the RoPE (``cfg.yarn_for``). A config with one kind builds
the programs it always did. MoE configs run the dropless expert layer
(``models/moe.moe_apply_dropless``) inside the layer's rest program; the
engine counts the (token, expert) rows dispatched on the host
(``EngineStats.expert_rows``) and the experts that received any row on the
device (``EngineStats.experts_hit``: a device scalar each layer program
adds to, read only when the stats are read). A dense config's programs
take and return no such scalar (``None``: no buffer).

**Timing.** The engine keeps a modeled clock (:meth:`ServeEngine.now`:
``um.clock`` under a UnifiedMemory, the step index otherwise, plus any
idle time skipped by :meth:`ServeEngine.advance_to`). Every request
records ``arrival_time`` at enqueue — NOT at admission — so TTFT
(``first_token_time - arrival_time``) includes the queueing delay a
request spends waiting for the admission gate; measuring from admission
would understate exactly the tail the SLO metrics exist to expose.
serve/traffic.py drives arrival processes against this clock and
serve/metrics.py aggregates the records into SLO reports.

Beside the modeled stamps each request carries wall-clock stamps
(``time.perf_counter()``): ``arrival_wall`` at enqueue, ``prefill_wall`` when
its first prefill chunk starts and ``first_token_wall`` when its first token
is sampled. ``prefill_wall - arrival_wall`` is the wait for the admission
gate and the shared prefill budget; ``first_token_wall - prefill_wall`` the
prefill itself.

**Programs.** A layer runs as three programs, in this order: the KV
pool's ``jit_kv_write`` (serve/paged.py), then in decode the paged kernel
(``jit_paged_attention``) and in prefill the pool's ``jit_kv_gather``, then
one program that runs the layer's output projection, residual and FFN and,
in the same program, the next layer's ``norm1`` and QKV projection
(``jit_layer_rest_qkv``, ``jit_prefill_layer_rest_qkv``; the last layer's
runs without it: ``jit_layer_rest``, ``jit_prefill_layer_rest``). The
embedding and layer 0's QKV are one program (``jit_embed_qkv``), and
``jit_greedy_next`` samples. A decode batch dispatches 3L+2 programs, a
prefill chunk 3L+1 and one more where it samples
(``EngineStats.programs``). The programs are composed, when they are
traced, from the engine's attributes ``_embed``, ``_qkv[kind]``,
``_decode_rest`` and ``_prefill_rest[kind]``; the paged kernel reads
``window[kind]`` at each call.

**Spans.** The engine's phases run inside host spans
(``jax.profiler.TraceAnnotation``, on the profiler's clock, a no-op costing
about a microsecond while no trace is taken): ``serve.step`` (arg ``step``)
around each step, ``serve.admit``, ``serve.prefill`` (``rid``, ``start``,
``end``) around a prefill chunk, ``serve.decode`` (``batch``) around a decode
batch, and inside those ``serve.embed`` (and in it ``serve.qkv``) around
the program that embeds and projects layer 0's QKV, ``serve.attention``
(arg ``window``) around the kernel, ``serve.layer_rest`` around each
layer's folded program and ``serve.sample`` around the sampling. The KV
pool adds ``serve.kv_write``/``kv_gather``/``kv_view`` (serve/paged.py) and
the memory model ``umem.*`` (core/umem.py).
"""
from __future__ import annotations

import contextlib
import functools
import time
from dataclasses import dataclass, field
from enum import Enum
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import HostSpillError, UnifiedMemory
from repro.kernels.paged_attention import paged_attention
from repro.models.attention import _causal_bias, _out_proj, _project_qkv, _sdpa
from repro.models.cache import kv_head_layout
from repro.models.layers import RunPolicy, apply_norm, mlp_apply
from repro.models import moe as moe_mod
from repro.models.transformer import embed_in, logits_out, policy_tp
from repro.serve.paged import PagedKVCache


# The pieces of a layer, each its own jitted program where it is called
# alone and inlined where the engine's folded programs call it (see the
# module doc's "Programs"): one compile per shape, where op-by-op dispatch
# compiled every op of the layer at every new shape.


def _embed(cfg, pol, params, toks, positions):
    return embed_in(cfg, params, toks, pol, positions)


def _layer_qkv(cfg, lay, yarn, p, x, positions):
    h = apply_norm(cfg.norm, x, p["norm1"])
    return _project_qkv(cfg, p["mixer"], h, lay, positions, yarn)


def _layer_rest(cfg, lay, pol, p, x, o, hits):
    """Residual add of the attention output ``o``, then the FFN block; adds
    to ``hits`` the experts that received a row (MoE)."""
    x = x + _out_proj(p["mixer"], o, lay)
    h2 = apply_norm(cfg.norm, x, p["norm2"])
    if cfg.is_moe:
        y, n = moe_mod.moe_apply_dropless(cfg, p["ffn"], h2)
        hits = hits + n
    else:
        y = mlp_apply(cfg, p["ffn"], h2, pol)
    return x + y, hits


def _prefill_layer_rest(cfg, lay, pol, window, p, x, q, k_full, v_full,
                        positions, kpos, hits):
    o = _sdpa(q, k_full[None], v_full[None],
              _causal_bias(positions, kpos, window))
    return _layer_rest(cfg, lay, pol, p, x, o, hits)


def _greedy_next(cfg, pol, params, x):
    """Greedy next token of each row from its last position: (B,)."""
    x = apply_norm(cfg.norm, x[:, -1:], params["final_norm"])
    return jnp.argmax(logits_out(cfg, params, x, pol)[:, -1], axis=-1)


def _program(name, fn, *bound):
    """``fn`` with its leading arguments bound, jitted under ``name``: the
    device trace shows ``jit_<name>`` (a bare partial shows ``jit__unknown``)."""
    f = functools.partial(fn, *bound)
    f.__name__ = name
    return jax.jit(f)


class SeqState(Enum):
    PENDING = "pending"      # not yet admitted
    PREFILL = "prefill"      # admitted, prompt partially prefilled
    DECODING = "decoding"    # generating tokens
    PREEMPTED = "preempted"  # KV swapped host-side, waiting to resume
    DONE = "done"


@dataclass
class Request:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    sid: int = -1
    state: SeqState = SeqState.PENDING
    prefill_pos: int = 0  # prompt tokens whose KV is in the pool
    saved: Optional[dict] = None  # host-side KV while preempted
    preemptions: int = 0
    recoveries: int = 0  # fault replays (KV lost, recomputed from prompt)
    tenant: str = ""
    # modeled-clock timestamps (engine.now()); TTFT anchors at arrival_time,
    # the enqueue instant, so pre-admission queueing delay is attributed to
    # the request
    arrival_time: float = 0.0
    admit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    # wall-clock stamps (time.perf_counter()): enqueue, start of the first
    # prefill chunk, first token
    arrival_wall: float = 0.0
    prefill_wall: Optional[float] = None
    first_token_wall: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.state is SeqState.DONE


@dataclass
class EngineStats:
    admitted: int = 0
    preempted: int = 0
    resumed: int = 0
    prefill_chunks: int = 0
    decode_batches: int = 0
    decode_tokens: int = 0
    # programs the steps dispatched, the engine's own and the KV pool's
    programs: int = 0
    # MoE: (token, expert) rows dispatched, and experts that received at
    # least one row, summed over layers and steps (on the device, read when
    # ServeEngine.stats is)
    expert_rows: int = 0
    experts_hit: int = 0
    # fault-recovery accounting (zero in a fault-free run)
    node_losses: int = 0
    recovered_requests: int = 0
    replayed_tokens: int = 0  # token work thrown away and recomputed
    # (prefilled prompt positions + generated tokens at replay time)
    spill_failures: int = 0
    admission_retries: int = 0  # admissions deferred by the post-fault hold
    lane_degraded_steps: int = 0


class ServeEngine:
    def __init__(self, cfg, params, *, max_seqs: int = 8, max_len: int = 512,
                 page_size: int = 64, num_pages: Optional[int] = None,
                 policy: Optional[RunPolicy] = None,
                 um: Optional[UnifiedMemory] = None, greedy: bool = True,
                 prefill_chunk: int = 128, watermark_pages: int = 0,
                 admit_device_fraction: float = 0.5,
                 counter_threshold: int = 16, mem_policy=None,
                 tp_plan=None, fault_plan=None,
                 admit_backoff_steps: int = 2):
        assert cfg.mixer == "attention", "paged serving targets attention archs"
        self.kinds = cfg.layer_kinds()
        assert set(self.kinds) <= {"attention", "local"}, \
            "paged serving runs global and sliding-window attention layers"
        self.cfg = cfg
        self.params = params
        self.policy = policy or RunPolicy()
        self.layout = kv_head_layout(cfg, policy_tp(self.policy))
        lay, pol = self.layout, self.policy
        # per layer kind: the window (0: global) and the programs it fixes
        self.window = {k: cfg.local_window if k == "local" else 0
                       for k in set(self.kinds)}
        self._embed = _program("embed", _embed, cfg, pol)
        self._qkv = {k: _program("layer_qkv", _layer_qkv, cfg, lay,
                                 cfg.yarn_for(k)) for k in self.window}
        self._prefill_rest = {k: _program("prefill_layer_rest",
                                          _prefill_layer_rest, cfg, lay, pol, w)
                              for k, w in self.window.items()}
        self._decode_rest = _program("layer_rest", _layer_rest, cfg, lay, pol)
        self._greedy_next = _program("greedy_next", _greedy_next, cfg, pol)
        # the programs the step dispatches, folded from the ones above: each
        # layer's kind and the next layer's (None after the last)
        self._next_kinds = list(zip(self.kinds, self.kinds[1:] + [None]))
        first = self.kinds[0]
        self._decode_head = _program("embed_qkv", self._head, first, True)
        self._prefill_head = _program("embed_qkv", self._head, first, False)
        self._decode_fold = {
            n: _program("layer_rest_qkv" if n else "layer_rest",
                        self._decode_layer, n)
            for _, n in self._next_kinds}
        self._prefill_fold = {
            (k, n): _program("prefill_layer_rest_qkv" if n
                             else "prefill_layer_rest", self._prefill_layer, k, n)
            for k, n in self._next_kinds}
        # tp_plan (e.g. repro.cluster.serve.ClusterTPPlan) maps sequences to
        # serving superchips and charges per-token tensor-parallel collective
        # traffic; it only ADDS modeled charges and node pins, so generated
        # tokens stay bit-identical to the single-node engine
        self.tp_plan = tp_plan
        seq_node = (tp_plan.node_of_seq if tp_plan is not None
                    and um is not None else None)
        self.cache = PagedKVCache(cfg, self.layout, max_seqs=max_seqs,
                                  max_len=max_len, page_size=page_size,
                                  num_pages=num_pages, um=um,
                                  counter_threshold=counter_threshold,
                                  mem_policy=mem_policy, seq_node=seq_node)
        self.um = um
        self.requests: Dict[int, Request] = {}
        self._next_rid = 0
        self.greedy = greedy
        self.max_len = max_len
        self.prefill_chunk = max(1, prefill_chunk)
        self.watermark_pages = watermark_pages
        self.admit_device_fraction = admit_device_fraction
        self._stats = EngineStats()
        # the device's count of experts hit (MoE); None threads no buffer
        self._experts_hit = (jnp.zeros((), jnp.int32) if cfg.is_moe
                             else None)
        self._needs_prefetch: List[Request] = []
        self._steps = 0
        self._idle_skipped = 0.0
        # fault plan (runtime/fault.py FaultPlan): a frozen, sorted schedule
        # this engine consumes through its own cursor, so one plan can be
        # shared across every engine of a traffic simulation. None costs a
        # single identity check per step — fault-free runs stay bit-identical
        if fault_plan is not None and not fault_plan:
            fault_plan = None  # empty plan: take the zero-cost path
        if fault_plan is not None and um is None:
            raise ValueError(
                "fault_plan needs a UnifiedMemory-governed engine: faults "
                "are delivered through um.fail_node / set_lane_degradation "
                "/ set_spill_failure")
        self.fault_plan = fault_plan
        self._fault_idx = 0
        self._degrade_until = -1  # step the active lane window expires at
        self._spill_until = -1    # step the active spill window expires at
        self.admit_backoff_steps = max(1, admit_backoff_steps)
        self._backoff = self.admit_backoff_steps
        self._hold_admit = 0  # steps fresh admission stays held post-fault
        self.draining = False

    # ------------------------------------------------------ folded programs
    # Traced inside the programs built in __init__: the jitted pieces they
    # call are inlined, read from the engine's attributes at trace time.
    def _qkv_of(self, kind, p, x, positions, decode: bool):
        """Layer ``kind``'s norm1 and QKV projection; in decode q comes out
        in the paged kernel's (B, n_q_eff, D)."""
        q, k, v = self._qkv[kind](p, x, positions)
        if decode:
            q = q.reshape(x.shape[0], self.layout.n_q_eff, self.cfg.head_dim)
        return q, k, v

    def _head(self, kind, decode, params, toks, positions):
        """The embedding, then layer 0's QKV: (x, q, k, v)."""
        x = self._embed(params, toks, positions)
        return (x,) + self._qkv_of(kind, params["layers"][0], x, positions,
                                   decode)

    def _decode_layer(self, nxt, p, x, o, hits, p_next, positions):
        """A decode layer after its attention output ``o`` (B, n_q_eff, D),
        then layer ``nxt``'s QKV: (x, hits, q, k, v), or (x, hits)."""
        x, hits = self._decode_rest(p, x, o[:, None], hits)
        if nxt is None:
            return x, hits
        return (x, hits) + self._qkv_of(nxt, p_next, x, positions, True)

    def _prefill_layer(self, kind, nxt, p, x, q, k_full, v_full, positions,
                       kpos, hits, p_next):
        """A prefill layer of ``kind`` from its QKV and the gathered prefix,
        then layer ``nxt``'s QKV: (x, hits, q, k, v), or (x, hits)."""
        x, hits = self._prefill_rest[kind](p, x, q, k_full, v_full,
                                           positions, kpos, hits)
        if nxt is None:
            return x, hits
        return (x, hits) + self._qkv_of(nxt, p_next, x, positions, False)

    # ----------------------------------------------------------------- clock
    def now(self) -> float:
        """Modeled time: the UnifiedMemory clock when one governs the pool
        (seconds of modeled kernel/migration time), the step index otherwise,
        plus idle time skipped via :meth:`advance_to`."""
        base = self.um.clock if self.um is not None else float(self._steps)
        return base + self._idle_skipped

    def advance_to(self, t: float) -> float:
        """Fast-forward the clock to ``t`` (an arrival-driven caller skipping
        idle time between the last completion and the next arrival). Never
        moves time backwards. Returns now()."""
        cur = self.now()
        if t > cur:
            self._idle_skipped += t - cur
        return self.now()

    # ---------------------------------------------------------------- admin
    def add_request(self, prompt: np.ndarray, max_new_tokens: int = 16, *,
                    arrival_time: Optional[float] = None,
                    tenant: str = "") -> int:
        rid = self._next_rid
        self._next_rid += 1
        # enqueue time IS the arrival: TTFT must cover pre-admission queueing
        self.requests[rid] = Request(
            rid, np.asarray(prompt), max_new_tokens, tenant=tenant,
            arrival_time=self.now() if arrival_time is None else arrival_time,
            arrival_wall=time.perf_counter())
        return rid

    def _in_state(self, state: SeqState) -> List[Request]:
        return [r for r in self.requests.values() if r.state is state]

    def _projected_kv_bytes(self, req: Request) -> int:
        """KV bytes this request still has to materialize: its full projected
        footprint (prompt + max_new_tokens, capped at max_len) minus the pool
        pages it already holds."""
        total = min(self.max_len, len(req.prompt) + req.max_new_tokens)
        have = (int(np.count_nonzero(self.cache.page_table[req.sid]))
                if req.sid >= 0 else 0)
        return max(0, self.cache.pages_for(total) - have) * self.cache.page_bytes

    # ----------------------------------------------------------- admission
    def _admission_ok(self, req: Request, running: List[Request]) -> bool:
        need = self.cache.pages_for(len(req.prompt)) + 1  # prompt + 1st decode
        if self.cache.free_pages() < need + self.watermark_pages:
            return False
        if self.um is not None and running and self.admit_device_fraction > 0:
            # memory-pressure gate: only admit while device memory can absorb
            # a fraction of the projected KV growth of this request PLUS what
            # the already-running sequences still have to materialize (skipped
            # when nothing runs, so pressure can never deadlock the engine)
            demand = self._projected_kv_bytes(req) + sum(
                self._projected_kv_bytes(r) for r in running)
            if self.um.device_free() < self.admit_device_fraction * demand:
                return False
        return True

    def _admit(self) -> int:
        progressed = 0
        running = self._in_state(SeqState.PREFILL) + \
            self._in_state(SeqState.DECODING)
        # resume preempted sequences first, oldest rid first (FIFO fairness:
        # a younger request never resumes past a stalled older one)
        for req in sorted(self._in_state(SeqState.PREEMPTED), key=lambda r: r.rid):
            if self.cache.free_slots() == 0:
                break
            need = self.cache.pages_for(int(req.saved["len"]) + 1)
            if self.cache.free_pages() < need + self.watermark_pages:
                break
            self._resume(req)
            running.append(req)
            progressed += 1
        if self._in_state(SeqState.PREEMPTED):
            return progressed  # don't admit fresh work while old work waits
        for req in sorted(self._in_state(SeqState.PENDING), key=lambda r: r.rid):
            if self.cache.free_slots() == 0:
                break
            # a fault-replayed request re-enters PENDING with its admit_time
            # already stamped; drain mode and the post-fault admission hold
            # apply only to genuinely fresh work, and skip (not break) so a
            # held fresh request never blocks a replayed one behind it
            fresh = req.admit_time is None
            if fresh and self.draining:
                continue
            if fresh and self._hold_admit > 0:
                self._stats.admission_retries += 1
                continue
            if not self._admission_ok(req, running):
                break
            req.sid = self.cache.new_seq()
            req.state = SeqState.PREFILL
            if req.admit_time is None:
                req.admit_time = self.now()
            self._stats.admitted += 1
            running.append(req)
            progressed += 1
        return progressed

    # ---------------------------------------------------------------- faults
    def start_drain(self) -> None:
        """Enter drain mode: in-flight requests run to completion, but no
        fresh request is admitted (fault-replayed requests still re-enter —
        they were already admitted once). run_to_completion then returns as
        soon as the admitted work finishes."""
        self.draining = True

    def _apply_faults(self) -> None:
        """Deliver the fault plan's due events for this step and expire any
        active lane-degradation / spill-failure window."""
        ev = self.fault_plan.events
        while self._fault_idx < len(ev) and ev[self._fault_idx].step <= self._steps:
            e = ev[self._fault_idx]
            self._fault_idx += 1
            if e.kind == "node_loss":
                self._on_node_loss(e.node)
            elif e.kind == "lane_degrade":
                self.um.set_lane_degradation(
                    (e.nvlink_factor, e.fabric_factor))
                self._degrade_until = e.step + e.duration
            elif e.kind == "spill_fail":
                self.um.set_spill_failure(True)
                self._spill_until = e.step + e.duration
            else:
                raise ValueError(f"unknown fault kind {e.kind!r}")
        if self._degrade_until >= 0:
            if self._steps >= self._degrade_until:
                self.um.set_lane_degradation(None)
                self._degrade_until = -1
            else:
                self._stats.lane_degraded_steps += 1
        if self._spill_until >= 0 and self._steps >= self._spill_until:
            self.um.set_spill_failure(False)
            self._spill_until = -1

    def _on_node_loss(self, node: int) -> None:
        """A serving superchip died: poison its resident pages, shrink the
        TP plan to the survivors, and replay every sequence whose KV pages
        are gone. Fresh admission backs off (doubling hold) so the shrunken
        pool re-stabilizes before taking new load."""
        self._stats.node_losses += 1
        lost = self.um.fail_node(node)
        if self.tp_plan is not None:
            self.tp_plan = self.tp_plan.without_node(node)
            # re-pin sequence placement to the surviving ranks
            self.cache.seq_node = self.tp_plan.node_of_seq
        runs = lost.get(self.cache.alloc.name, [])
        for sid in self.cache.seqs_touching_pages(runs):
            req = next((r for r in self.requests.values()
                        if r.sid == sid and not r.done), None)
            if req is not None:
                self._replay(req)
        self._hold_admit = max(self._hold_admit, self._backoff)
        self._backoff = min(self._backoff * 2, 64)

    def _replay(self, req: Request) -> None:
        """Drop a sequence whose KV is lost (or unsavable) and requeue it
        for recompute from its prompt. Greedy decode is per-row batch-
        independent, so the replayed tokens come back bit-identical to the
        lost ones — the fault regression test pins the full stream against
        a fault-free run."""
        self._stats.recovered_requests += 1
        self._stats.replayed_tokens += len(req.generated) + req.prefill_pos
        if req.sid >= 0:
            self.cache.release(req.sid)
            req.sid = -1
        req.saved = None
        req.generated = []
        req.prefill_pos = 0
        req.state = SeqState.PENDING
        req.recoveries += 1

    # ---------------------------------------------------------- preemption
    def _node_ctx(self, sid: int):
        """Pin umem ops to the sequence's serving superchip under a TP plan
        (node-aware pools spill/promote as seen from that node)."""
        if self.tp_plan is not None and self.um is not None:
            return self.um.on_node(self.tp_plan.node_of_seq(sid))
        return contextlib.nullcontext()

    def _preempt(self, req: Request) -> None:
        if self.um is not None:
            try:
                with self._node_ctx(req.sid):
                    for band in self.cache.seq_views(req.sid):
                        self.um.demote(band)
            except HostSpillError:
                # spill window active: the KV cannot be saved host-side.
                # Fall back to dropping it and recomputing from the prompt
                # — greedy decode replays bit-identically, so correctness
                # survives at a recompute (not preemption) cost
                self._stats.spill_failures += 1
                self._replay(req)
                return
        req.saved = self.cache.swap_out(req.sid)
        req.sid = -1
        req.state = SeqState.PREEMPTED
        req.preemptions += 1
        self._stats.preempted += 1

    def _resume(self, req: Request) -> None:
        req.sid = self.cache.swap_in(req.saved)
        req.saved = None
        # a sequence preempted mid-prefill picks its prompt back up
        req.state = (SeqState.DECODING if req.prefill_pos == len(req.prompt)
                     else SeqState.PREFILL)
        self._stats.resumed += 1
        if self.um is not None:
            self._needs_prefetch.append(req)

    def _prefetch_resumed(self) -> None:
        """Promote resumed sequences' extents ahead of their decode turn."""
        if self.um is None or not self._needs_prefetch:
            self._needs_prefetch = []
            return
        todo, self._needs_prefetch = self._needs_prefetch, []
        # per-request issue, pinned to each sequence's serving node; the
        # per-band charges accrue in the same order the flattened single
        # prefetch_async call used, so single-node charges are unchanged
        for req in todo:
            if req.sid < 0:
                continue
            bands = self.cache.seq_views(req.sid)
            if bands:
                with self._node_ctx(req.sid):
                    self.um.prefetch_async(bands)

    # -------------------------------------------------------------- prefill
    def _prefill_step(self) -> int:
        budget = self.prefill_chunk
        chunks = 0
        for req in sorted(self._in_state(SeqState.PREFILL), key=lambda r: r.rid):
            if budget == 0:
                break
            want = min(budget, len(req.prompt) - req.prefill_pos)
            # clamp the chunk to the pages the pool can back right now,
            # keeping one page in reserve per decoding sequence so prefill
            # never starves the decode batch of its new-token pages; a
            # stalled chunk retries next step once decode frees pages
            reserve = len(self._in_state(SeqState.DECODING))
            afford = (self.cache.allocated_until(req.sid)
                      + max(0, self.cache.free_pages() - reserve)
                      * self.cache.page_size
                      - req.prefill_pos)
            chunk = min(want, afford)
            if chunk <= 0:
                continue
            self._prefill_chunk_run(req, chunk)
            budget -= chunk
            chunks += 1
        return chunks

    def _prefill_chunk_run(self, req: Request, chunk: int) -> None:
        s = req.prefill_pos
        e = s + chunk
        if req.prefill_wall is None:
            req.prefill_wall = time.perf_counter()
        with TraceAnnotation("serve.prefill", rid=req.rid, start=s, end=e):
            self.cache.alloc_range(req.sid, s, e)
            toks = np.asarray(req.prompt[s:e], np.int32)[None, :]
            positions, kpos = jax.device_put(
                (np.arange(s, e, dtype=np.int32), np.arange(e, dtype=np.int32)))
            layers, hits = self.params["layers"], self._experts_hit
            with TraceAnnotation("serve.embed"), TraceAnnotation("serve.qkv"):
                x, q, k_new, v_new = self._prefill_head(
                    self.params, toks, positions)
            for i, (kind, next_kind) in enumerate(self._next_kinds):
                self.cache.write_at(req.sid, i, k_new, v_new, s)
                k_full, v_full = self.cache.gather_kv(req.sid, i, e)
                with TraceAnnotation("serve.layer_rest"):
                    x, hits, *qkv = self._prefill_fold[kind, next_kind](
                        layers[i], x, q, k_full, v_full, positions, kpos,
                        hits, layers[i + 1] if next_kind else None)
                if qkv:
                    q, k_new, v_new = qkv
            self._count_experts(chunk, hits)
            # the head and a fold a layer; step() counts the pool's programs
            self._stats.programs += len(self.kinds) + 1
            req.prefill_pos = e
            self.cache.commit_prefill(req.sid, e)
            if self.tp_plan is not None:
                self.tp_plan.on_prefill(self, chunk)
            self._stats.prefill_chunks += 1
            if e == len(req.prompt):
                with TraceAnnotation("serve.sample"):
                    req.generated.append(
                        int(np.asarray(self._greedy_next(self.params, x))[0]))
                self._stats.programs += 1
                if req.first_token_time is None:
                    req.first_token_time = self.now()
                    req.first_token_wall = time.perf_counter()
                req.state = SeqState.DECODING
                if (len(req.generated) >= req.max_new_tokens
                        or len(req.prompt) + len(req.generated)
                        >= self.max_len - 1):
                    self._finish(req)

    # --------------------------------------------------------------- decode
    def _ensure_decode_pages(self, reqs: List[Request]) -> List[Request]:
        """Back every batch member's new-token page, preempting the youngest
        page-holding sequences (their KV demoted host-side) when the pool
        runs dry. Victims may be decoding OR mid-prefill — only the oldest
        page-holder is shielded, so it always makes progress."""
        reqs = sorted(reqs, key=lambda r: r.rid)
        while True:
            need = sum(1 for r in reqs
                       if self.cache.missing_pages(
                           r.sid, int(self.cache.lengths[r.sid]) + 1))
            if need <= self.cache.free_pages():
                break
            holders = sorted(
                (r for r in self.requests.values() if r.sid >= 0
                 and r.state in (SeqState.DECODING, SeqState.PREFILL)),
                key=lambda r: r.rid)
            if len(holders) <= 1:
                raise RuntimeError(
                    "KV page pool too small for a single sequence: "
                    f"num_pages={self.cache.num_pages}, "
                    f"seq needs page {int(self.cache.lengths[reqs[0].sid]) + 1}")
            victim = holders[-1]  # youngest first: the oldest always runs
            self._preempt(victim)
            if victim in reqs:
                reqs.remove(victim)
            if not reqs:
                return reqs  # whole batch preempted; the oldest is prefilling
        for r in reqs:
            self.cache.alloc_range(r.sid, 0, int(self.cache.lengths[r.sid]) + 1)
        return reqs

    def _decode_batch(self, reqs: List[Request]) -> None:
        B = len(reqs)
        with TraceAnnotation("serve.decode", batch=B):
            sids = [r.sid for r in reqs]
            pos = [int(self.cache.lengths[r.sid]) for r in reqs]
            tokens = np.asarray([[r.generated[-1]] for r in reqs], np.int32)
            positions = jax.device_put(np.asarray(pos, np.int32)[:, None])
            pt, ln = self.cache.batch_view(sids)

            layers, hits = self.params["layers"], self._experts_hit
            with TraceAnnotation("serve.embed"), TraceAnnotation("serve.qkv"):
                x, q, k_new, v_new = self._decode_head(
                    self.params, tokens, positions)
            for i, (kind, next_kind) in enumerate(self._next_kinds):
                self.cache.write_token(sids, i, k_new, v_new, pos)
                window = self.window[kind]
                with TraceAnnotation("serve.attention", window=window):
                    o = paged_attention(q, self.cache.k_pools[i],
                                        self.cache.v_pools[i], pt, ln,
                                        window=window)
                with TraceAnnotation("serve.layer_rest"):
                    x, hits, *qkv = self._decode_fold[next_kind](
                        layers[i], x, o, hits,
                        layers[i + 1] if next_kind else None, positions)
                if qkv:
                    q, k_new, v_new = qkv
            self._count_experts(B, hits)
            # the head, a kernel and a fold a layer, the sampler; step()
            # counts the pool's programs
            self._stats.programs += 2 * len(self.kinds) + 2
            with TraceAnnotation("serve.sample"):
                nxt = np.asarray(self._greedy_next(self.params, x))
            self.cache.commit_token(sids, pos)
            if self.tp_plan is not None:
                self.tp_plan.on_decode(self, len(reqs))
            self._stats.decode_batches += 1
            self._stats.decode_tokens += len(reqs)
            for r, t in zip(reqs, nxt):
                r.generated.append(int(t))
                total = len(r.prompt) + len(r.generated)
                if (len(r.generated) >= r.max_new_tokens
                        or total >= self.max_len - 1):
                    self._finish(r)

    @property
    def stats(self) -> EngineStats:
        """The counters, with the device's count of experts hit read in."""
        if self._experts_hit is not None:
            self._stats.experts_hit = int(self._experts_hit)
        return self._stats

    def _count_experts(self, tokens: int, hits) -> None:
        self._stats.expert_rows += tokens * self.cfg.top_k * self.cfg.num_layers
        self._experts_hit = hits

    def _finish(self, req: Request) -> None:
        req.state = SeqState.DONE
        req.finish_time = self.now()
        if req.sid >= 0:
            self.cache.release(req.sid)
            req.sid = -1

    # ------------------------------------------------------------------ run
    def _in_flight(self) -> bool:
        if self.draining:
            # fresh never-admitted requests are not in flight while draining
            # — they will not be admitted, so waiting on them would stall
            return any(not r.done and not (r.state is SeqState.PENDING
                                           and r.admit_time is None)
                       for r in self.requests.values())
        return any(not r.done for r in self.requests.values())

    def step(self) -> bool:
        """One engine step: admit/resume, chunked prefill, prefetch, decode.
        Returns True while any request is in flight."""
        with TraceAnnotation("serve.step", step=self._steps):
            pool0 = self.cache.kv_pool_calls
            if self.fault_plan is not None:
                self._apply_faults()
            pre0 = self._stats.preempted
            rec0 = self._stats.recovered_requests
            progress = 0
            if self._hold_admit > 0:
                # the post-fault backoff window ticking down IS forward
                # motion: held admissions land when it expires
                self._hold_admit -= 1
                progress += 1
                if self._hold_admit == 0:
                    self._backoff = self.admit_backoff_steps
            with TraceAnnotation("serve.admit"):
                progress += self._admit()
            progress += self._prefill_step()
            decoding = self._in_state(SeqState.DECODING)
            if decoding:
                batch = self._ensure_decode_pages(decoding)
                if batch:
                    self._prefetch_resumed()
                    self._decode_batch(batch)
                    progress += len(batch)
            # a preemption frees pages for next step's admit/prefill/decode,
            # so it counts as progress (a genuine deadlock preempts nothing
            # either); likewise a fault replay requeues real work for the
            # next step
            progress += self._stats.preempted - pre0
            progress += self._stats.recovered_requests - rec0
            if self.um is not None:
                self.um.sync()  # sync point: apply counter-driven delayed migrations
            self._stats.programs += self.cache.kv_pool_calls - pool0
            self._steps += 1
            in_flight = self._in_flight()
        if in_flight and progress == 0:
            raise RuntimeError(
                "scheduler stalled: KV pool cannot back any in-flight request "
                f"(free_pages={self.cache.free_pages()}, "
                f"states={[r.state.value for r in self.requests.values()]})")
        return in_flight

    def run_to_completion(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        steps = 0
        while self.step():
            steps += 1
            if steps > max_steps:
                raise RuntimeError("serve did not converge")
        return {rid: r.generated for rid, r in self.requests.items()}
