"""From a JAX profiler trace to device busy time, idle share, time by
program or operation, exposed collective time and idle gaps by host span.

A device is a plane named ``/device:TPU:<n>``. Its line ``XLA Ops`` holds
one event per operation that ran; ``XLA Modules`` one per program (a
jitted function's name, ``jit_<name>``). Host spans are the harness's
``TraceAnnotation`` events whose names start with ``bench.``.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "allreduce", "allgather")
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint intervals ``a`` that no interval of the
    disjoint ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def short_name(line: str, name: str) -> str:
    """``jit_f(123..)`` -> ``jit_f``; ``%fusion.3 = bf16[..] fusion(..)`` ->
    ``fusion.3``."""
    if line == "XLA Modules":
        return re.sub(r"\(\d+\)$", "", name)
    return name.split(" = ")[0].lstrip("%")


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


@dataclass
class Device:
    name: str
    ops: List[Tuple[str, float, float]] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def busy(self) -> List[Interval]:
        return union([(s, e) for _, s, e in self.ops])

    def collective_exposed(self) -> float:
        coll = union([(s, e) for n, s, e in self.ops if is_collective(n)])
        comp = union([(s, e) for n, s, e in self.ops if not is_collective(n)])
        return length(subtract(coll, comp))


@dataclass
class Summary:
    devices: List[Device]
    spans: List[Tuple[str, float, float]]  # host spans, seconds
    window: Interval                       # host seconds of the traced window

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        w = [self.window]
        return sum(length(subtract(w, subtract(w, d.busy)))
                   for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def seconds_by(self, kind: str = "modules") -> Dict[str, float]:
        """Device seconds per program (``modules``) or operation (``ops``),
        averaged over the devices."""
        acc: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for n, s, e in getattr(d, kind):
                acc[n] += (e - s) / len(self.devices)
        return dict(acc)

    def matching(self, needle: str, kind: str = "modules") -> Optional[float]:
        """Seconds of the programs or operations whose name holds
        ``needle``; None where none ran."""
        hits = [v for k, v in self.seconds_by(kind).items() if needle in k]
        return sum(hits) if hits else None

    def collective_exposed_share(self, device: int = 0) -> float:
        return self.devices[device].collective_exposed() / self.window_s

    def idle_gaps(self, device: int = 0, top: int = 10):
        """Idle seconds of one device inside the window, by the innermost
        host span in which each gap began (``other`` outside any)."""
        w = [self.window]
        gaps = subtract(w, self.devices[device].busy)
        acc: Dict[str, float] = defaultdict(float)
        for a, b in gaps:
            inner = [(e - s, n) for n, s, e in self.spans if s <= a < e]
            acc[min(inner)[1] if inner else "other"] += b - a
        return sorted(([k, v] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:top]

    def top(self, kind: str = "modules", n: int = 10):
        return sorted(([k, v] for k, v in self.seconds_by(kind).items()),
                      key=lambda kv: -kv[1])[:n]


def reduce_file(path: str, window: Optional[Interval] = None) -> Summary:
    """Read one ``.xplane.pb``. Times are seconds on the trace's clock;
    ``window`` defaults to the span of the device events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device(plane.name)
            for line in plane.lines:
                dst = {"XLA Ops": dev.ops, "XLA Modules": dev.modules}.get(line.name)
                if dst is None:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dst.append((short_name(line.name, ev.name), s,
                                s + ev.duration_ns * 1e-9))
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((ev.name[len(SPAN_PREFIX):], s,
                                      s + ev.duration_ns * 1e-9))
    if not devices:
        raise ValueError(f"{path}: no /device:TPU plane in the trace")
    if window is None:
        ev = [x for d in devices for x in d.ops]
        window = (min(s for _, s, _ in ev), max(e for _, _, e in ev))
    return Summary(devices, spans, window)


class Tracer:
    """A profiler session over the window, written to a temporary directory
    outside the checkout and removed once reduced. The window is the span
    named ``bench.window`` that the caller opens around the measured steps."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # Python function tracing slows the host
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> Summary:
        import jax

        jax.profiler.stop_trace()
        try:
            path = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            s = reduce_file(path)
            win = [x for x in s.spans if x[0] == "window"]
            if win:
                s.window = (win[0][1], win[0][2])
            return s
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
