"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
event per executed HLO operation, named by the operation's HLO text
(``%fusion.3 = bf16[...] fusion(...)``); a Pallas kernel shows as a custom
call named after its jitted wrapper (``%lattice_encode_pallas.1 = ...``).
Host threads are lines of ``/host:CPU``; the benchmark's own
``TraceAnnotation`` spans (``bench.*``) sit there on the same clock.

Everything here works on plain ``(start_ns, end_ns)`` intervals so it can be
checked on a synthetic trace.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|send|recv)"
                        r"(-start|-done)?(\.\d+)?$")

Interval = tuple[float, float]


def op_name(event_name: str) -> str:
    """``%lattice_encode_pallas.1 = u32[...] custom-call(...)`` ->
    ``lattice_encode_pallas``; ``%fusion.3 = ...`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def union(intervals: Iterable[Interval]) -> list[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> list[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


@dataclasses.dataclass
class DeviceOps:
    """One device's executed operations: (name, start_ns, end_ns)."""
    device: int
    ops: list[tuple[str, float, float]]

    def busy(self, lo: float, hi: float) -> list[Interval]:
        return union(clip(((a, b) for _, a, b in self.ops), lo, hi))

    def kernel_ns(self, pattern: str, lo: float, hi: float) -> float:
        """Summed device time of the operations whose name matches."""
        rx = re.compile(pattern)
        return total(union(clip(((a, b) for n, a, b in self.ops
                                 if rx.search(n)), lo, hi)))

    def exposed_collective_ns(self, lo: float, hi: float) -> float:
        """Time in which a collective runs and no other operation does."""
        coll = union(clip(((a, b) for n, a, b in self.ops
                           if COLLECTIVE.match(n)), lo, hi))
        other = union(clip(((a, b) for n, a, b in self.ops
                            if not COLLECTIVE.match(n)), lo, hi))
        return total(subtract(coll, other))


@dataclasses.dataclass
class Trace:
    devices: list[DeviceOps]
    spans: list[tuple[str, float, float]]     # host bench.* annotations
    window: Interval                          # the measured window

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_ns(self) -> float:
        """Busy time averaged over the devices used."""
        lo, hi = self.window
        return (sum(total(d.busy(lo, hi)) for d in self.devices)
                / max(len(self.devices), 1))

    def idle_share(self) -> float:
        return 1.0 - self.busy_ns() / self.window_ns

    def kernel_ns(self, pattern: str) -> float:
        """Device time of matching kernels, averaged over the devices."""
        lo, hi = self.window
        return (sum(d.kernel_ns(pattern, lo, hi) for d in self.devices)
                / max(len(self.devices), 1))

    def exposed_collective_ns_worst(self) -> float:
        lo, hi = self.window
        return max((d.exposed_collective_ns(lo, hi) for d in self.devices),
                   default=0.0)

    def span_ns(self, name: str) -> float:
        lo, hi = self.window
        return total(clip(((a, b) for n, a, b in self.spans if n == name),
                          lo, hi))

    def top_ops(self, k: int = 10) -> list[list]:
        """The device operations that took most time (seconds, averaged
        over the devices), by operation name."""
        lo, hi = self.window
        acc: dict[str, float] = {}
        for d in self.devices:
            for n, a, b in clip3(d.ops, lo, hi):
                acc[n] = acc.get(n, 0.0) + (b - a)
        nd = max(len(self.devices), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / nd / 1e9] for n, t in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """Device-idle time of the first device, attributed to the innermost
        host span open at each gap's midpoint (``host.other`` where none
        is), summed by span name; the longest ``k``."""
        lo, hi = self.window
        if not self.devices:
            return []
        gaps = subtract([(lo, hi)], self.devices[0].busy(lo, hi))
        # sweep: span starts (0), gap midpoints (1), span ends (2); the
        # benchmark's spans come from one thread, so they nest and the
        # innermost open span is the top of a stack
        events = [(a, 0, i) for i, (_, a, _) in enumerate(self.spans)]
        events += [(b, 2, i) for i, (_, _, b) in enumerate(self.spans)]
        events += [(0.5 * (a + b), 1, j) for j, (a, b) in enumerate(gaps)]
        events.sort()
        stack: list[int] = []
        acc: dict[str, float] = {}
        for _, kind, i in events:
            if kind == 0:
                stack.append(i)
            elif kind == 2:
                if i in stack:
                    stack.remove(i)
            else:
                name = self.spans[stack[-1]][0] if stack else "host.other"
                a, b = gaps[i]
                acc[name] = acc.get(name, 0.0) + (b - a)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / 1e9] for n, t in top]


def clip3(ops, lo, hi):
    for n, a, b in ops:
        a2, b2 = max(a, lo), min(b, hi)
        if b2 > a2:
            yield n, a2, b2


def from_profile(pd, devices: Optional[set[int]] = None,
                 window_span: str = "bench.window") -> Trace:
    """Build a :class:`Trace` from ``jax.profiler.ProfileData``.

    ``devices`` limits the device planes to those ids (the chips the cell
    uses).  The window is the benchmark's ``window_span`` annotation; without
    one it is the extent of all device operations."""
    devs = []
    spans = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((op_name(e.name), float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
            devs.append(DeviceOps(dev, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    devs.sort(key=lambda d: d.device)
    win = [s for s in spans if s[0] == window_span]
    if win:
        window = (win[0][1], win[0][2])
    else:
        starts = [a for d in devs for _, a, _ in d.ops]
        ends = [b for d in devs for _, _, b in d.ops]
        window = (min(starts, default=0.0), max(ends, default=0.0))
    return Trace(devs, spans, window)


def load(path: str, devices: Optional[set[int]] = None) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path), devices)
