"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device operations are the events of each TPU plane's ``XLA Ops`` line;
the benchmark's own host spans are ``TraceAnnotation`` events of the
host plane.  The window runs from the first span's start to the last
span's end.  Busy time is the union of a device's operation intervals
inside the window, averaged over the devices; every stretch of the
window in which a device ran nothing is an idle gap, named by the host
span that overlaps it most.

Operations nest on that line (a ``while`` holds its body's
operations), so a layer's device time is the union of its operations'
intervals, and the breakdown lists leaf operations only, by their HLO
name and output shape.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass

DEVICE_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


@dataclass
class Op:
    name: str
    start: int          # ns
    end: int
    device: str
    module: str = ""


@dataclass
class Span:
    name: str
    start: int
    end: int


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Reduced:
    def __init__(self, ops, spans):
        self.ops = ops
        self.spans = sorted(spans, key=lambda s: s.start)
        if not self.spans:
            raise ValueError("the trace holds none of the benchmark's spans")
        self.start = self.spans[0].start
        self.end = max(s.end for s in self.spans)
        self.window_s = (self.end - self.start) * 1e-9
        devices = sorted({o.device for o in ops})
        self.devices = devices
        self._busy = {d: _merge((max(o.start, self.start),
                                 min(o.end, self.end))
                                for o in ops if o.device == d
                                and o.end > self.start
                                and o.start < self.end)
                      for d in devices}
        busy = [sum(e - s for s, e in iv) for iv in self._busy.values()]
        self.busy_s = (sum(busy) / len(busy) * 1e-9) if busy else 0.0

    def count(self, span: str) -> int:
        return sum(s.name == span for s in self.spans)

    def op_seconds(self, pred) -> float:
        """Device seconds in the window during which an operation
        matching ``pred`` ran, averaged over the devices."""
        tot = 0
        for d in self.devices:
            tot += sum(e - s for s, e in _merge(
                (max(o.start, self.start), min(o.end, self.end))
                for o in self.ops if o.device == d and pred(o)
                and o.end > self.start and o.start < self.end))
        return tot * 1e-9 / max(1, len(self.devices))

    def leaves(self):
        """The operations that hold no other operation."""
        out = []
        for d in self.devices:
            ops = sorted((o for o in self.ops if o.device == d),
                         key=lambda o: (o.start, o.start - o.end))
            for a, b in zip(ops, ops[1:] + [None]):
                if b is None or b.start >= a.end:
                    out.append(a)
        return out

    def gaps(self):
        """(start, end) of every idle stretch of the first device."""
        if not self.devices:
            return [(self.start, self.end)]
        out, t = [], self.start
        for s, e in self._busy[self.devices[0]]:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.end:
            out.append((t, self.end))
        return out

    def gap_owner(self, gap) -> str:
        best, name = 0, "outside spans"
        for s in self.spans:
            ov = min(s.end, gap[1]) - max(s.start, gap[0])
            if ov > best:
                best, name = ov, s.name
        return name

    def breakdown(self, top: int = 10) -> dict:
        per = {}
        for o in self.leaves():
            if o.end > self.start and o.start < self.end:
                name = short_name(o.name)
                per[name] = per.get(name, 0) + (
                    min(o.end, self.end) - max(o.start, self.start))
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, t * 1e-9 / max(1, len(self.devices))]
                               for n, t in ops],
                "idle_gaps": [[self.gap_owner(g), (g[1] - g[0]) * 1e-9]
                              for g in gaps]}


def short_name(hlo: str) -> str:
    """``%fusion.150 = u32[6291456,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.150 u32[6291456,128]``."""
    name, _, rest = hlo.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{name.lstrip('%')} {shape}".strip()


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:
        return {}


def reduce_profile(pd, span_names) -> Reduced:
    ops, spans, modules = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name == DEVICE_LINE:
                    for e in line.events:
                        st = _stats(e)
                        ops.append(Op(e.name, int(e.start_ns),
                                      int(e.start_ns + e.duration_ns),
                                      plane.name,
                                      str(st.get("hlo_module", ""))))
                elif line.name == MODULE_LINE:
                    modules.extend(Span(e.name, int(e.start_ns),
                                        int(e.start_ns + e.duration_ns))
                                   for e in line.events)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        spans.append(Span(e.name, int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
    for o in ops:
        if not o.module:
            for m in modules:
                if m.start <= o.start < m.end:
                    o.module = m.name
                    break
    return Reduced(ops, spans)


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def reduce_file(path: str, span_names) -> Reduced:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path),
                          span_names)


def reduce_dir(trace_dir: str, span_names) -> Reduced:
    return reduce_file(find_xplane(trace_dir), span_names)
