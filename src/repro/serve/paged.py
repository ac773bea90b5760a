"""Paged KV cache: a software page table for serving, umem-integrated.

The pool is one allocation in the UnifiedMemory runtime: page residency
(HBM vs host), access counters and migrations follow the paper's system-
memory policy by default — hot sequences' pages migrate device-side, cold
ones are read remotely (``mem_policy`` swaps the pool onto any registered
backend, see docs/memspace.md). kernels/paged_attention consumes the pool
directly.

The pool may be allocated *larger than device capacity* (``num_pages``):
under the system policy first-touch simply maps the overflow host-side and
decode runs with remote KV pages — the paper's graceful-oversubscription
behavior (§7) applied to serving. The scheduler in serve/engine.py drives
the lifecycle: sequences that lose their pool pages to preemption are
swapped out host-side (``swap_out``) and scattered back on resume
(``swap_in``), at which point the access-counter path re-promotes their
pages.

Each layer's pool is head-major, (num_pages, N, page_size, D): one page of
one KV head is a contiguous (page_size, D) tile, the block the paged
attention kernel DMAs per grid step. Writes and gathers index (page, :,
slot), so callers still see KV as (tokens, N, D).

Every write and gather is one jitted program over a layer's K and V pools
(``kv_write``, ``kv_gather``: the device trace shows ``jit_kv_write`` and
``jit_kv_gather``). The write donates the pools, so its scatter runs in
place instead of copying a whole pool, and covers exactly the tokens given:
a partial tail page is never padded. The layer is no argument of either
program, so one compile per (token count, pool shape) serves every layer.
Their page and slot indices are checked and built on the host, then
uploaded once and reused by every layer that asks for the same ones: a
decode batch uploads one index pair for all layers, a prefill chunk one for
its writes and one for its prefix gathers (``kv_index_uploads`` against
``kv_pool_calls``).

Writes, gathers and the decode batch's page-table view run inside host
spans (``serve.kv_write``, ``serve.kv_gather``, ``serve.kv_view``; see the
"Spans" part of serve/engine.py's docstring).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import (Actor, BufferView, KernelBatch, MemPolicy,
                        UnifiedMemory, coalesce_runs, make_policy,
                        system_policy)
from repro.models.layout import HeadLayout


def _rows(pool, pids, slots):
    """Index of every (token, head) row of a (P, N, page_size, D) pool.

    Each index picks one contiguous D-row, so the TPU compiler scatters and
    gathers in the pool's own layout; indexing (pids, :, slots) instead has
    it relayout the whole pool around every scatter and gather."""
    heads = jnp.arange(pool.shape[1])[None, :]
    return pids[:, None], heads, slots[:, None]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def kv_write(k_pool, v_pool, pids, slots, k, v):
    """Scatter tokens' K and V into one layer's pools at (pids, :, slots).

    k, v: (T, N, D), or with a unit axis the engine's programs leave on them,
    (T, 1, N, D) from decode or (1, T, N, D) from prefill."""
    N, D = k_pool.shape[1], k_pool.shape[3]
    rows = _rows(k_pool, pids, slots)
    return (k_pool.at[rows].set(k.reshape(-1, N, D)),
            v_pool.at[rows].set(v.reshape(-1, N, D)))


@jax.jit
def kv_gather(k_pool, v_pool, pids, slots):
    """One layer's K and V at (pids, :, slots): (T, N, D) each."""
    rows = _rows(k_pool, pids, slots)
    return k_pool[rows], v_pool[rows]


class PagedKVCache:
    @staticmethod
    def page_bytes_for(cfg, layout: HeadLayout, page_size: int,
                       dtype=jnp.float32) -> int:
        """Bytes of one pool page (k+v, all layers) — usable without building
        the pools, e.g. to size a modeled device capacity."""
        return (2 * cfg.num_layers * page_size * layout.n_kv_eff
                * cfg.head_dim * jnp.dtype(dtype).itemsize)

    def __init__(self, cfg, layout: HeadLayout, *, max_seqs: int, max_len: int,
                 page_size: int = 64, num_pages: Optional[int] = None,
                 dtype=jnp.float32, um: Optional[UnifiedMemory] = None,
                 counter_threshold: int = 16,
                 mem_policy: "MemPolicy | str | None" = None,
                 seq_node=None):
        self.cfg = cfg
        self.layout = layout
        # sid -> issuing superchip for node-aware pools (None: ambient node).
        # Tracked launches over a sequence's pages are pinned through this,
        # so first touch places each sequence's KV on its serving node.
        self.seq_node = seq_node
        self.page_size = page_size
        self.max_seqs = max_seqs
        self.pages_per_seq = -(-max_len // page_size)
        self.num_pages = num_pages or (max_seqs * self.pages_per_seq + 1)
        N, D = layout.n_kv_eff, cfg.head_dim
        L = cfg.num_layers
        self.k_pools = [jnp.zeros((self.num_pages, N, page_size, D), dtype)
                        for _ in range(L)]
        self.v_pools = [jnp.zeros((self.num_pages, N, page_size, D), dtype)
                        for _ in range(L)]
        self.page_table = np.zeros((max_seqs, self.pages_per_seq), np.int32)
        self.lengths = np.zeros((max_seqs,), np.int32)
        self.active = np.zeros((max_seqs,), bool)
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))  # 0 = null
        # device copies of the last (pids, slots) written and gathered, keyed
        # on their host bytes, and how often a pool call had to upload them
        self._dev_idx: Dict[str, Tuple[bytes, object]] = {}
        self.kv_pool_calls = 0
        self.kv_index_uploads = 0

        self.um = um
        self.page_bytes = self.page_bytes_for(cfg, layout, page_size, dtype)
        if um is not None:
            # serving pages are big (page_bytes >> the HW remote-access grain),
            # so one decode touch of a remote page already counts several
            # transactions — a low threshold keeps the counter path responsive.
            # The pool is a typed buffer (num_pages x page_bytes), the same
            # front-end the paper apps use: one umem page per pool page, and
            # buf.rows(lo, hi) is the extent of a pool-page run.
            # mem_policy opens the pool to other registered backends: a
            # MemPolicy instance is used AS-IS — it carries its own
            # threshold, and counter_threshold only applies when mem_policy
            # is None or a registry name whose factory takes the knob — and
            # its page_size must equal page_bytes; a registry name is built
            # at pool-page granularity.
            if mem_policy is None:
                mem_policy = system_policy(page_size=self.page_bytes,
                                           threshold=counter_threshold)
            elif isinstance(mem_policy, str):
                mem_policy = make_policy(mem_policy, page_size=self.page_bytes,
                                         threshold=counter_threshold)
            assert mem_policy.paged, \
                f"KV pool needs a paged backend; {mem_policy.kind!r} has no " \
                "page table (its swap/demote/extent paths cannot work)"
            assert mem_policy.page_size == self.page_bytes, \
                f"pool policy must be paged at one umem page per KV pool " \
                f"page ({mem_policy.kind!r} came back with page_size=" \
                f"{mem_policy.page_size}, pool pages are {self.page_bytes} B " \
                "— its factory must honor the page_size knob)"
            self.buf = um.array("kv_pool", (self.num_pages, self.page_bytes),
                                np.uint8, mem_policy)
            self.alloc = self.buf.alloc

    # ------------------------------------------------------------- slots
    def free_slots(self) -> int:
        return int(np.count_nonzero(~self.active))

    def free_pages(self) -> int:
        return len(self._free)

    def pages_for(self, ntok: int) -> int:
        return -(-ntok // self.page_size)

    def new_seq(self) -> int:
        sid = int(np.nonzero(~self.active)[0][0])
        self.active[sid] = True
        self.lengths[sid] = 0
        self.page_table[sid] = 0
        return sid

    def release(self, sid: int) -> None:
        row = self.page_table[sid]
        self._free.extend(int(p) for p in row[row != 0])
        self.active[sid] = False
        self.page_table[sid] = 0
        self.lengths[sid] = 0

    # ------------------------------------------------------- page accounting
    def alloc_range(self, sid: int, start: int, end: int) -> None:
        """Ensure pages backing positions [start, end) are allocated.

        Vectorized: all holes fill from the free stack in one shot, in the
        exact order sequential pop() calls would have used (so pool page ids
        — and therefore the umem pool's run layout — are unchanged)."""
        j0, j1 = start // self.page_size, -(-end // self.page_size)
        row = self.page_table[sid, j0:j1]
        holes = np.flatnonzero(row == 0)
        if len(holes):
            assert len(self._free) >= len(holes), "page pool exhausted"
            row[holes] = self._free[:-len(holes) - 1:-1]
            del self._free[-len(holes):]

    def missing_pages(self, sid: int, end: int) -> int:
        """Pages still unallocated among those backing positions [0, end)."""
        j1 = min(self.pages_per_seq, -(-end // self.page_size))
        return int(np.count_nonzero(self.page_table[sid, :j1] == 0))

    def allocated_until(self, sid: int) -> int:
        """First position not covered by an already-allocated page."""
        row = self.page_table[sid]
        holes = np.flatnonzero(row == 0)
        j = int(holes[0]) if len(holes) else self.pages_per_seq
        return j * self.page_size

    def _flat_idx(self, sid: int, start: int, n: int):
        pos = start + np.arange(n)
        pids = self.page_table[sid, pos // self.page_size]
        assert (pids != 0).all(), "write into unallocated page"
        return pids, pos % self.page_size

    def _device_idx(self, kind: str, pids, slots):
        """(pids, slots) on the device, uploaded only where they differ from
        the last ones of this ``kind``. Keyed on the indices themselves, not
        on the sequence and positions: a released and reused sid maps the
        same positions to other pages."""
        self.kv_pool_calls += 1
        pids = np.asarray(pids, np.int32)
        slots = np.asarray(slots, np.int32)
        key = pids.tobytes() + slots.tobytes()
        hit = self._dev_idx.get(kind)
        if hit is None or hit[0] != key:
            self.kv_index_uploads += 1
            hit = self._dev_idx[kind] = (key, jax.device_put((pids, slots)))
        return hit[1]

    def _write(self, layer: int, pids, slots, k, v) -> None:
        self.k_pools[layer], self.v_pools[layer] = kv_write(
            self.k_pools[layer], self.v_pools[layer],
            *self._device_idx("write", pids, slots), k, v)

    # ------------------------------------------------------------- writes
    def write_at(self, sid: int, layer: int, k, v, start: int) -> None:
        """Scatter S tokens' KV at positions [start, start+S) of sequence sid.

        k, v: (S, N, D) or (1, S, N, D). One scatter per pool in one jitted
        program — every page of the chunk lands at once, and the update
        covers exactly S slots (a partial tail page is never zero-padded)."""
        with TraceAnnotation("serve.kv_write"):
            pids, slots = self._flat_idx(sid, start, k.shape[-3])
            self._write(layer, pids, slots, k, v)

    def write_prefill(self, sid: int, layer: int, k, v) -> None:
        """k, v: (S, N, D) for one sequence; fills positions [0, S)."""
        S = k.shape[0]
        self.alloc_range(sid, 0, S)
        self.write_at(sid, layer, k, v, 0)
        if layer == self.cfg.num_layers - 1:
            self.commit_prefill(sid, S)

    def commit_prefill(self, sid: int, new_len: int) -> None:
        self.lengths[sid] = new_len
        self._touch(sid)

    def write_token(self, sid_list, layer: int, k, v, pos_list) -> None:
        """k, v: (B, N, D) or (B, 1, N, D) new-token KV for sequences
        sid_list at pos_list."""
        with TraceAnnotation("serve.kv_write"):
            sids = np.asarray(sid_list)
            pos = np.asarray(pos_list)
            pids = self.page_table[sids, pos // self.page_size]
            assert (pids != 0).all(), "decode write into unallocated page"
            self._write(layer, pids, pos % self.page_size, k, v)

    def commit_token(self, sid_list, pos_list) -> None:
        # lengths first, then one batched engine step over every decoded
        # sequence's pool pages: sids are unique within a decode batch, so
        # each kv_seq launch sees exactly the views the sequential
        # touch-per-sequence loop would have (charges are bit-identical)
        for s, p in zip(sid_list, pos_list):
            self.lengths[s] = p + 1
        if self.um is None:
            return
        batch = KernelBatch()
        for s in sid_list:
            views = self.seq_views(s)
            if views:
                batch.launch(f"kv_seq{s}", reads=views, actor=Actor.GPU,
                             node=self._node_of(s))
        if len(batch):
            self.um.launch_batch(batch)

    # ------------------------------------------------------------- reads
    def gather_kv(self, sid: int, layer: int, length: int):
        """Gather positions [0, length) of sequence sid -> (length, N, D) pair."""
        with TraceAnnotation("serve.kv_gather"):
            pids, slots = self._flat_idx(sid, 0, length)
            return kv_gather(self.k_pools[layer], self.v_pools[layer],
                             *self._device_idx("gather", pids, slots))

    # ------------------------------------------------------------- swap
    def swap_out(self, sid: int) -> Dict[str, object]:
        """Demote a sequence host-side: copy its KV out of the pool and release
        every pool page. Returns the saved state for swap_in."""
        L = int(self.lengths[sid])
        pairs = [self.gather_kv(sid, layer, L)
                 for layer in range(self.cfg.num_layers)]
        self.release(sid)
        return {"len": L, "k": [np.asarray(k) for k, _ in pairs],
                "v": [np.asarray(v) for _, v in pairs]}

    def swap_in(self, saved: Dict[str, object]) -> int:
        """Re-admit a swapped-out sequence: allocate fresh pages and scatter the
        saved KV back into the pool. Returns the new sid."""
        sid = self.new_seq()
        L = int(saved["len"])
        self.alloc_range(sid, 0, L)
        for layer in range(self.cfg.num_layers):
            self.write_at(sid, layer, saved["k"][layer], saved["v"][layer], 0)
        self.lengths[sid] = L
        return sid

    # ------------------------------------------------------------- umem
    def close(self) -> None:
        """Free the pool's UnifiedMemory allocation. Residency (host and
        device) must return to its pre-pool baseline — the serve-path
        clause of the policy-conformance contract pins this symmetry."""
        if self.um is not None:
            self.um.free(self.alloc)

    def _seq_page_runs(self, sid: int) -> List[Tuple[int, int]]:
        """[lo, hi) pool-page runs of the sequence, consecutive pages
        coalesced (the allocator is mostly sequential, so a sequence usually
        collapses to a handful of runs)."""
        npages = -(-int(self.lengths[sid]) // self.page_size)
        pids = np.sort(self.page_table[sid, :npages].astype(np.int64))
        return coalesce_runs(pids[pids != 0])

    def seq_views(self, sid: int) -> List[BufferView]:
        """The sequence's pool pages as buffer row bands — what the engine
        hands to um.demote / um.prefetch_async and _touch launches over."""
        return [self.buf.rows(s, e) for s, e in self._seq_page_runs(sid)]

    def seq_extents(self, sid: int) -> List[Tuple[int, int]]:
        """Byte extents of the sequence's pool pages (coalesced runs)."""
        return [(s * self.page_bytes, e * self.page_bytes)
                for s, e in self._seq_page_runs(sid)]

    def seqs_touching_pages(self, runs) -> List[int]:
        """Active sequence ids whose pool pages intersect the given [lo, hi)
        pool-page runs. The pool is paged at one umem page per pool page, so
        the poisoned runs ``um.fail_node`` reports for the pool allocation
        index pool pages directly — the engine replays the sequences this
        returns from their prompts."""
        if not runs:
            return []
        dead = np.zeros(self.num_pages, bool)
        for s, e in runs:
            dead[int(s):int(e)] = True
        out = []
        for sid in np.flatnonzero(self.active):
            row = self.page_table[sid]
            pids = row[row != 0]
            if len(pids) and dead[pids].any():
                out.append(int(sid))
        return out

    def _node_of(self, sid: int):
        return None if self.seq_node is None else self.seq_node(sid)

    def _touch(self, sid: int) -> None:
        if self.um is None:
            return
        # account page-granular access in the unified-memory runtime: batch
        # every resident page of the sequence into ONE tracked launch
        views = self.seq_views(sid)
        if views:
            self.um.launch(f"kv_seq{sid}", reads=views, actor=Actor.GPU,
                           node=self._node_of(sid))

    # ------------------------------------------------------------- views
    def batch_view(self, sids):
        """A decode batch's page-table rows and the keys each sequence
        attends, its new token's included (length + 1), on the device."""
        with TraceAnnotation("serve.kv_view"):
            return (jnp.asarray(self.page_table[sids]),
                    jnp.asarray(self.lengths[sids] + 1))
