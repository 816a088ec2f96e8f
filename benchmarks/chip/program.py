"""The program's own spans and counters (``repro.obs``), as the benchmark
reads them.

Two readings:

1. ``traced_window`` asks the program what its spans and counters saw over
   the traced steps of a run, on the host clock.  The per-layer metrics
   ``prepare_ms``, ``sample_ms``, ``decode_call_ms``,
   ``host_syncs_per_step`` and ``programs_built_per_step`` divide it by the
   decode steps the engine counted there.  A program without ``repro.obs``
   gives None, and so do the metrics.
2. ``load_xplane`` reads a profiler trace as ``trace.load_xplane`` does and
   keeps, besides, the program's host spans (``engine.*``, ``disagg.*``,
   ``prefill.*``) and each device operation's named-scope path.
   ``ProgramReduced`` adds to ``trace.Reduced``: time inside a program
   span, a device's idle time by the innermost program span open, and
   device time by named scope.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from benchmarks.chip import trace
from benchmarks.chip.trace import Span

PROGRAM_PREFIXES = ("engine.", "disagg.", "prefill.")
# the program's named scopes (``jax.named_scope`` in src/repro), innermost
# first when an operation sits under several
SCOPES = ("m2n_dispatch", "m2n_combine", "router", "experts", "combine",
          "attention", "embed", "lm_head", "attn", "expert")
STEP = "engine.step"
STEP_SELF = "engine.step (self)"
OUTSIDE = "outside the engine"
MODULES_LINE = "XLA Modules"
# an HLO instruction and the op_name of its metadata, in printed HLO
_INSTR = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"', re.M)


# --------------------------------------------------------------------------
# 1. the program's counts over the traced steps
# --------------------------------------------------------------------------

def traced_window(rec) -> Optional[Tuple[dict, dict]]:
    """(span totals, counters) that the program's recorders closed and
    counted within the traced steps, or None where the program keeps no
    such record or decoded nothing there."""
    try:
        from repro import obs
    except ImportError:
        return None
    steps = rec["traced_steps"]
    if not steps:
        return None
    totals, counters = obs.window(steps[0].t0, steps[-1].t1)
    if not counters.get("decode_steps"):
        return None
    return totals, counters


def ms_per_step(rec, span: str) -> Optional[float]:
    """Host milliseconds inside ``span`` per decode step of the traced
    window."""
    w = traced_window(rec)
    if w is None or span not in w[0]:
        return None
    totals, counters = w
    return 1e3 * totals[span][1] / counters["decode_steps"]


def count_per_step(rec, counter: str) -> Optional[float]:
    """A program counter's rise per decode step of the traced window."""
    w = traced_window(rec)
    if w is None:
        return None
    return w[1].get(counter, 0) / w[1]["decode_steps"]


# --------------------------------------------------------------------------
# 2. program spans and named scopes on the profiler's clock
# --------------------------------------------------------------------------

@dataclass
class ScopedOp(trace.Op):
    scope: str = ""              # the op's named-scope path ('' if none)


@dataclass
class ProgramIntervals(trace.Intervals):
    """``trace.Intervals`` with the program's host spans beside the
    harness's, and a scope on each device operation."""
    program_spans: List[Span] = field(default_factory=list)

    def to_json(self) -> dict:
        out = super().to_json()
        out["ops"] = {str(k): [[o.name, o.start, o.end,
                                getattr(o, "scope", "")] for o in v]
                      for k, v in self.ops.items()}
        out["program_spans"] = [[s.name, s.start, s.end]
                                for s in self.program_spans]
        return out

    @classmethod
    def from_json(cls, obj: dict) -> "ProgramIntervals":
        return cls(ops={int(k): [ScopedOp(*o) for o in v]
                        for k, v in obj["ops"].items()},
                   spans=[Span(*s) for s in obj["spans"]],
                   program_spans=[Span(*s)
                                  for s in obj.get("program_spans", [])])


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    n = shift = 0
    while True:
        c = buf[i]
        n |= (c & 0x7F) << shift
        i += 1
        shift += 7
        if c < 0x80:
            return n, i


def _fields(buf: bytes):
    """(field number, value) of each field of one protobuf message; a
    length-delimited value as bytes."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def module_scopes(path: str) -> Dict[str, Dict[str, str]]:
    """Program name as the device's ``XLA Modules`` line gives it
    (``jit_scan(<id>)``) -> {HLO instruction: its named-scope path}, read
    from the HLO protos that the profiler writes into the trace's
    ``/host:metadata`` plane when ``enable_hlo_proto`` is on.  (XSpace 1:
    planes; XPlane 2: name, 4: event metadata, 5: stat metadata;
    XEventMetadata 2: name, 5: stats; XStat 1: metadata id, 6: bytes;
    HloProto 1: the module.)"""
    from jax._src.lib import xla_client as xc
    with open(path, "rb") as f:
        space = f.read()
    opts = xc._xla.HloPrintOptions.short_parsable()
    opts.print_metadata = True
    out: Dict[str, Dict[str, str]] = {}
    for num, plane in _fields(space):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if dict(fields).get(2) != b"/host:metadata":
            continue
        stat_names = {}
        for num2, entry in fields:
            if num2 == 5:
                md = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[md.get(1, 0)] = md.get(2, b"").decode()
        for num2, entry in fields:
            if num2 != 4:
                continue
            meta = list(_fields(dict(_fields(entry)).get(2, b"")))
            name = dict(meta).get(2, b"").decode()
            for num3, stat in meta:
                st = dict(_fields(stat)) if num3 == 5 else {}
                if stat_names.get(st.get(1)) == "Hlo Proto" and 6 in st:
                    module = dict(_fields(st[6])).get(1, b"")
                    text = xc.XlaComputation(module).as_hlo_module() \
                        .to_string(opts)
                    out[name] = dict(_INSTR.findall(text))
    return out


def load_xplane(path: str) -> ProgramIntervals:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    scopes = module_scopes(path)
    out = ProgramIntervals()
    for plane in data.planes:
        dev = trace._device_id(plane.name)
        if dev is not None:
            modules = sorted(
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events)
            starts = [m[0] for m in modules]
        for line in plane.lines:
            if dev is not None and line.name == trace.OPS_LINE:
                out.ops.setdefault(dev, []).extend(
                    ScopedOp(trace.op_name(e.name), e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9,
                             _scope_of(e, starts, modules, scopes))
                    for e in line.events)
            elif dev is None and plane.name.startswith("/host:"):
                for e in line.events:
                    s = Span(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                    if e.name.startswith(trace.SPAN_PREFIX):
                        out.spans.append(s)
                    elif e.name.startswith(PROGRAM_PREFIXES):
                        out.program_spans.append(s)
    for ops in out.ops.values():
        ops.sort(key=lambda o: o.start)
    out.spans.sort(key=lambda s: (s.start, -s.end))
    out.program_spans.sort(key=lambda s: (s.start, -s.end))
    return out


def _scope_of(event, starts, modules, scopes) -> str:
    """The named-scope path of one device operation: found in the HLO of
    the program whose ``XLA Modules`` event holds the operation's start."""
    i = bisect.bisect_right(starts, event.start_ns) - 1
    if i < 0 or event.start_ns >= modules[i][1]:
        return ""
    return scopes.get(modules[i][2], {}).get(trace.op_name(event.name), "")


def scope_label(path: str) -> str:
    """The innermost of the program's scopes on a named-scope path."""
    parts = path.split("/")
    for part in reversed(parts):
        if part in SCOPES:
            return part
    return "unscoped"


class ProgramReduced(trace.Reduced):
    """``trace.Reduced`` over ``ProgramIntervals``."""

    def program_span_time_s(self, name: str) -> float:
        """Time inside program spans of that name, within the window."""
        return sum(b - a for a, b in trace.union(
            [c for s in self.iv.program_spans if s.name == name
             for c in [trace.clip(s.start, s.end, self.t0, self.t1)] if c]))

    def _segments(self) -> List[Tuple[float, float, str]]:
        """The window cut where any program span opens or closes, each
        piece labelled by the innermost program span open over it."""
        spans = [s for s in self.iv.program_spans
                 if s.end > self.t0 and s.start < self.t1]
        cuts = sorted({self.t0, self.t1} | {
            t for s in spans for t in (s.start, s.end)
            if self.t0 < t < self.t1})
        out, i, open_ = [], 0, []
        for a, b in zip(cuts, cuts[1:]):
            while i < len(spans) and spans[i].start <= a:
                open_.append(spans[i])
                i += 1
            open_ = [s for s in open_ if s.end > a]
            inner = min(open_, key=lambda s: s.end - s.start, default=None)
            label = (OUTSIDE if inner is None else
                     STEP_SELF if inner.name == STEP else inner.name)
            out.append((a, b, label))
        return out

    def idle_by_program_span(self, dev: int, n: int = 12) -> List[list]:
        """Idle time of device ``dev`` summed by the innermost program span
        open over it, largest first."""
        busy = trace.union([c for _, c in self._ops(dev)])
        tot: Dict[str, float] = defaultdict(float)
        j = 0
        for a, b, label in self._segments():
            # idle = the segment less the busy intervals that meet it
            while j < len(busy) and busy[j][1] <= a:
                j += 1
            t, k = a, j
            while k < len(busy) and busy[k][0] < b:
                if busy[k][0] > t:
                    tot[label] += busy[k][0] - t
                t = max(t, busy[k][1])
                k += 1
            if t < b:
                tot[label] += b - t
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def device_time_by_scope(self) -> List[list]:
        """Device time of the operations under each of the program's named
        scopes, averaged over the window's devices, largest first."""
        tot: Dict[str, float] = defaultdict(float)
        for d in self.devices:
            for o in self.iv.ops.get(d, []):
                c = trace.clip(o.start, o.end, self.t0, self.t1)
                if c is not None:
                    tot[scope_label(getattr(o, "scope", ""))] += (
                        (c[1] - c[0]) / len(self.devices))
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])]
