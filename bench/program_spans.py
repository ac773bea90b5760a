"""The device's idle time split by what the host was doing, from the
program's own host spans.

The serve engine opens ``serve.*`` spans (serve/engine.py, serve/paged.py)
and the memory model ``umem.*`` spans (core/umem.py), on the profiler's
clock. Each idle interval of the device is cut at the spans' edges, and
each piece goes to the innermost span open over it (the one opened last),
whose name gives the bucket:

- ``dispatch``: ``serve.embed``, ``qkv``, ``attention``, ``layer_rest``,
  ``sample``, and the self time of ``serve.prefill`` and ``serve.decode``;
- ``kv_pool``: ``serve.kv_*``;
- ``umem``: ``umem.*``;
- ``scheduler``: ``serve.admit`` and the self time of ``serve.step``;
- ``outside_step``: no ``serve.step`` open.

The buckets add up to the window's idle time.
"""
from __future__ import annotations

import heapq
from typing import Dict, List, Tuple

from bench.trace_reduce import Interval, subtract

PREFIXES = ("serve.", "umem.")
BUCKETS = ("dispatch", "kv_pool", "umem", "scheduler", "outside_step")
STEP = "serve.step"

Span = Tuple[str, float, float]


def bucket(name: str) -> str:
    """The bucket of the innermost open span ``name``."""
    if name.startswith("umem."):
        return "umem"
    if name.startswith("serve.kv_"):
        return "kv_pool"
    if name in (STEP, "serve.admit"):
        return "scheduler"
    return "dispatch"


def read_spans(path: str) -> List[Span]:
    """The program's host spans in one ``.xplane.pb``: (name, start, end),
    seconds on the trace's clock."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        s = ev.start_ns * 1e-9
                        out.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def idle_split(idle: List[Interval], spans: List[Span]) -> Dict[str, float]:
    """Seconds of the disjoint intervals ``idle`` by bucket: one sweep over
    the sorted edges of the intervals and the spans, the open spans in a
    heap keyed by (latest start, earliest end)."""
    spans = [x for x in spans if x[2] > x[1]]
    edges = [(a, 1, -1) for a, _ in idle] + [(b, 0, -1) for _, b in idle]
    for i, (_, s, e) in enumerate(spans):
        edges += [(s, 1, i), (e, 0, i)]
    edges.sort()
    acc = dict.fromkeys(BUCKETS, 0.0)
    heap: List[Tuple[float, float, int]] = []
    open_ = [False] * len(spans)
    steps, idle_on, t0 = 0, False, 0.0
    for t, opening, i in edges:
        if idle_on and t > t0:
            if steps:  # an open serve.step keeps the heap from emptying
                while not open_[heap[0][2]]:
                    heapq.heappop(heap)
                acc[bucket(spans[heap[0][2]][0])] += t - t0
            else:
                acc["outside_step"] += t - t0
        t0 = t
        if i < 0:
            idle_on = bool(opening)
            continue
        name, s, e = spans[i]
        open_[i] = bool(opening)
        if opening:
            heapq.heappush(heap, (-s, e, i))
        if name == STEP:
            steps += 1 if opening else -1
    return acc


def idle_in(summary, spans: List[Span]) -> Dict[str, float]:
    """``idle_in.<bucket>``: the first device's idle seconds in each bucket
    over the traced window's seconds of a ``bench.trace_reduce.Summary``,
    in %."""
    idle = subtract([summary.window], summary.devices[0].busy)
    return {f"idle_in.{k}": 100.0 * v / summary.window_s
            for k, v in idle_split(idle, spans).items()}
