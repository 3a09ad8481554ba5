"""From a ``jax.profiler`` trace to the numbers per-layer metrics read.

``load`` keeps three kinds of events, in seconds on the trace's clock:
the device's operations (chip 0's "XLA Ops" line), the benchmark's own
call spans (``SPAN``, one per search call), and the host events of the
thread that made those calls, which say what the host was doing while the
device sat idle.  ``View`` holds them for one traced window with the
reductions the metric readers use.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Optional

SPAN = "chipbench.search"
DEVICE_PLANE = "/device:TPU:0"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str           # a device op's HLO name ("fusion.3"), or a host event
    start: float
    dur: float
    module: str = ""    # the program a device op ran in ("jit_search_stacked")
    label: str = ""     # a device op's name and result shape, for reading

    @property
    def end(self) -> float:
        return self.start + self.dur


def union(intervals, lo: float, hi: float) -> list:
    """Merged (start, end) pairs of ``intervals`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The idle (start, end) pairs between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def deepest_at(events, t: float) -> Optional[Event]:
    """The innermost (latest-starting) event that covers time t."""
    best = None
    for ev in events:
        if ev.start <= t < ev.end and (best is None or ev.start >= best.start):
            best = ev
    return best


@dataclasses.dataclass
class View:
    """One traced window: device ops, call spans and host events inside
    it, the queries those calls carried, and the work they needed."""
    ops: list
    spans: list
    host: list
    lo: float
    hi: float
    queries: int
    work: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)
    _busy: Optional[list] = dataclasses.field(default=None, repr=False)
    _ends: list = dataclasses.field(default_factory=list, repr=False)

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    @property
    def calls(self) -> int:
        return len(self.spans)

    def busy(self, lo: Optional[float] = None,
             hi: Optional[float] = None) -> list:
        """Merged busy intervals, clipped to [lo, hi] (default: the
        window)."""
        if self._busy is None:
            self._busy = union(((e.start, e.end) for e in self.ops),
                               self.lo, self.hi)
            self._ends = [e for _, e in self._busy]
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        out = []
        for s, e in self._busy[bisect.bisect_right(self._ends, lo):]:
            if s >= hi:
                break
            out.append((max(s, lo), min(e, hi)))
        return out

    def busy_s(self, lo=None, hi=None) -> float:
        return sum(e - s for s, e in self.busy(lo, hi))

    def op_time(self, pred) -> float:
        """Summed device time of the ops for which ``pred(event)`` holds."""
        return sum(e.dur for e in self.ops if pred(e))

    def top_ops(self, n: int = 10) -> list:
        acc = {}
        for e in self.ops:
            acc[e.label or e.name] = acc.get(e.label or e.name, 0.0) + e.dur
        return sorted(([k, v] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest idle gaps, each named by what the host thread that
        drives the calls was doing at its middle."""
        longest = sorted(gaps(self.busy(), self.lo, self.hi),
                         key=lambda g: g[0] - g[1])[:n]
        out = []
        for s, e in longest:
            ev = deepest_at(self.host, (s + e) / 2)
            out.append([ev.name if ev is not None else "(no host event)",
                        e - s])
        return out


def _device_ops(line_events, module_events) -> list:
    """Device ops with their HLO names and the program each ran in.  An
    op's event is named by its HLO instruction ("%fusion.3 = f32[..]
    fusion(..)"); its program is the "XLA Modules" event around it."""
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                   ev.name.split("(")[0]) for ev in module_events)
    starts = [m[0] for m in mods]
    out = []
    for ev in line_events:
        text = ev.name
        name = text.split(" = ")[0].lstrip("%")
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        module = mods[i][2] if i >= 0 and ev.start_ns < mods[i][1] else ""
        out.append(Event(name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                         module, text.split("{")[0].lstrip("%")))
    return out


def load(trace_dir: str):
    """(device ops, call spans, host events of the calling thread) from
    the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir},"
                                f" found {files}")
    prof = ProfileData.from_file(files[0])
    ops, spans, host = [], [], []
    for plane in prof.planes:
        if plane.name == DEVICE_PLANE:
            lines = {line.name: list(line.events) for line in plane.lines}
            ops.extend(_device_ops(lines.get(OPS_LINE, []),
                                   lines.get(MODULES_LINE, [])))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [Event(ev.name, ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9) for ev in line.events]
                mine = [e for e in evs if e.name == SPAN]
                if mine:
                    spans.extend(mine)
                    host.extend(evs)
    return ops, spans, host


def view(ops, spans, host, *, queries: int, work=None,
         peaks=None) -> View:
    """The window from the first call span's start to the last one's end,
    with everything clipped to it; ``queries`` is what the spans' calls
    carried in all."""
    if not spans:
        raise ValueError("the trace holds no call span")
    lo = min(s.start for s in spans)
    hi = max(s.end for s in spans)
    inside = [e for e in ops if e.end > lo and e.start < hi]
    return View(ops=inside, spans=sorted(spans, key=lambda s: s.start),
                host=[e for e in host if e.end > lo and e.start < hi],
                lo=lo, hi=hi, queries=queries,
                work=work or {}, peaks=peaks or {})
