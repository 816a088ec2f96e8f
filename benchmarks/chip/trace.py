"""Reduce a profiler trace to intervals, and intervals to shares.

Two stages, so that the second can be checked on a small recorded trace
without the profiler:

1. ``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
   and keeps what the metrics need: each device's operations (name,
   start, end, in seconds) and the harness's host spans (``bench.*``).
2. ``Reduced`` answers questions about one window of those intervals:
   busy time per device, the summed time of operations whose name holds a
   pattern, the largest operations, and the idle gaps labelled by the
   innermost host span that was open at the gap's midpoint.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
Interval = Tuple[float, float]


@dataclass
class Op:
    name: str
    start: float
    end: float


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Intervals:
    """Device operations per device id, and the harness's host spans."""
    ops: Dict[int, List[Op]] = field(default_factory=dict)
    spans: List[Span] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"ops": {str(k): [[o.name, o.start, o.end] for o in v]
                        for k, v in self.ops.items()},
                "spans": [[s.name, s.start, s.end] for s in self.spans]}

    @classmethod
    def from_json(cls, obj: dict) -> "Intervals":
        return cls(ops={int(k): [Op(*o) for o in v]
                        for k, v in obj["ops"].items()},
                   spans=[Span(*s) for s in obj["spans"]])

    def save(self, path: str):
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path: str) -> "Intervals":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))


def _device_id(plane_name: str) -> Optional[int]:
    """'/device:TPU:3' -> 3; None for host and other planes."""
    if not plane_name.startswith("/device:TPU:"):
        return None
    tail = plane_name.rsplit(":", 1)[1]
    return int(tail) if tail.isdigit() else None


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """'%fusion.16 = bf16[8,256]{...} fusion(...)' -> 'fusion.16'."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> Intervals:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = Intervals()
    for plane in data.planes:
        dev = _device_id(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == OPS_LINE:
                out.ops.setdefault(dev, []).extend(
                    Op(op_name(e.name), e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events)
            elif dev is None and plane.name.startswith("/host:"):
                out.spans.extend(
                    Span(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    for ops in out.ops.values():
        ops.sort(key=lambda o: o.start)
    out.spans.sort(key=lambda s: (s.start, -s.end))
    return out


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def clip(a: float, b: float, lo: float, hi: float) -> Optional[Interval]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


class Reduced:
    """The intervals of one window [t0, t1] (seconds, the trace's clock)."""

    def __init__(self, iv: Intervals, t0: float, t1: float,
                 devices: Optional[List[int]] = None):
        self.iv, self.t0, self.t1 = iv, t0, t1
        self.devices = sorted(iv.ops) if devices is None else list(devices)

    @classmethod
    def for_span(cls, iv: Intervals, span_name: str,
                 devices: Optional[List[int]] = None) -> "Reduced":
        """The window of the (first) host span of that name."""
        for s in iv.spans:
            if s.name == span_name:
                return cls(iv, s.start, s.end, devices)
        raise ValueError(f"no host span {span_name!r} in the trace")

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def _ops(self, dev: int):
        for o in self.iv.ops.get(dev, []):
            c = clip(o.start, o.end, self.t0, self.t1)
            if c is not None:
                yield o.name, c

    def busy_s(self, dev: int) -> float:
        return sum(b - a for a, b in union([c for _, c in self._ops(dev)]))

    def mean_busy_s(self, devices: Optional[List[int]] = None) -> float:
        devs = self.devices if devices is None else devices
        return sum(self.busy_s(d) for d in devs) / len(devs)

    def idle_share(self, devices: Optional[List[int]] = None) -> float:
        return 1.0 - self.mean_busy_s(devices) / self.window_s

    def op_time_s(self, pattern: str,
                  devices: Optional[List[int]] = None) -> float:
        """Summed device time of the operations whose name holds
        ``pattern``, over the given devices."""
        devs = self.devices if devices is None else devices
        return sum(b - a for d in devs for n, (a, b) in self._ops(d)
                   if pattern in n)

    def top_ops(self, n: int = 10) -> List[list]:
        """The operations that took most device time, averaged over the
        window's devices."""
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for name, (a, b) in self._ops(d):
                tot[name] += (b - a) / len(self.devices)
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def span_time_s(self, name: str) -> float:
        """Time inside host spans of that name, within the window."""
        return sum(b - a for a, b in union(
            [c for s in self.iv.spans if s.name == name
             for c in [clip(s.start, s.end, self.t0, self.t1)] if c]))

    def _label(self, t: float) -> str:
        """The innermost harness span open at time t ('client' if none
        but the window's own)."""
        best = None
        for s in self.iv.spans:
            if s.start <= t < s.end and (best is None
                                         or s.end - s.start
                                         < best.end - best.start):
                best = s
        if best is None or (best.start <= self.t0 and best.end >= self.t1):
            return "client"
        return best.name[len(SPAN_PREFIX):]

    def idle_gaps(self, dev: int, n: int = 10) -> List[list]:
        """Idle time of device ``dev`` summed by what the host was doing,
        largest first."""
        busy = union([c for _, c in self._ops(dev)])
        gaps, t = [], self.t0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < self.t1:
            gaps.append((t, self.t1))
        tot: Dict[str, float] = defaultdict(float)
        for a, b in gaps:
            tot[self._label((a + b) / 2)] += b - a
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
