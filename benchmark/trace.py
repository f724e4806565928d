"""From a profiler trace to the intervals the per-layer readers take.

The device-plane arithmetic follows the program's `utils/profiling.py`
(`overlap_stats`, `op_breakdown`): a collective is recognized by its op
kind; compute comes from the synchronous "XLA Ops" line; communication
from any op line, where an async collective spans start to done. It is
re-derived here on `jax.profiler.ProfileData`, so that the program can
change and this reduction cannot. All times are nanoseconds."""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

# HLO op kinds that move data between devices
COMM_RE = re.compile(r"collective-permute|all-reduce|all-gather|all-to-all"
                     r"|reduce-scatter|ppermute|send|recv", re.IGNORECASE)
_KIND_RE = re.compile(r"\s([a-z][a-z0-9._-]*)\(")
# control-flow ops whose events hold the ops of their body
CONTAINERS = ("while", "conditional", "call")
# the benchmark's own host spans (`harness.py`)
SPAN_PREFIX = "bench."
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# lines that repeat what the op lines hold
_SKIP_LINES = ("XLA Modules", "Steps", "Framework Ops", "TC Overlay")


def op_kind(name: str) -> str:
    """Short op kind of an HLO event name: '%fusion.3 = f32[..] fusion(..)'
    and 'fusion.3' both give 'fusion'."""
    rhs = name.split(" = ", 1)[-1]
    if rhs.startswith("("):  # tuple type: skip past it
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    m = _KIND_RE.search(" " + rhs)
    if m:
        return m.group(1)
    return re.sub(r"\.\d+$", "", name.split("(")[0].strip() or name)


def short_name(name: str) -> str:
    """'%closed_call.8 = f32[..] custom-call(..), ..' -> '%closed_call.8
    custom-call'."""
    return f"{name.split(' = ', 1)[0]} {op_kind(name)}"


def is_comm(name: str) -> bool:
    return bool(COMM_RE.search(op_kind(name)))


@dataclass
class Device:
    """One device plane: ``ops`` of the "XLA Ops" line (control-flow
    containers left out, names shortened), ``comm`` spans of collectives
    on any op line, ``modules`` (program executions)."""
    name: str
    ops: list = field(default_factory=list)      # (name, start, end)
    comm: list = field(default_factory=list)     # (start, end)
    modules: list = field(default_factory=list)  # (name, start, end)


@dataclass
class Trace:
    devices: list
    spans: list  # (name, start, end) host spans of the benchmark


def load(path: str) -> Trace:
    """Read one ``*.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = Device(plane.name.replace("/device:", ""))
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev.modules = [(e.name, e.start_ns, e.end_ns)
                                   for e in line.events]
                    continue
                if line.name in _SKIP_LINES:
                    continue
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    if is_comm(e.name):
                        dev.comm.append((e.start_ns, e.end_ns))
                    elif (line.name == OPS_LINE
                          and op_kind(e.name) not in CONTAINERS):
                        dev.ops.append((short_name(e.name), e.start_ns,
                                        e.end_ns))
            if dev.ops or dev.modules:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(sorted(devices, key=lambda d: d.name), sorted(
        spans, key=lambda s: s[1]))


# -- interval arithmetic (lists of (start, end), merged = sorted, disjoint)

def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def intersect(a, b) -> list:
    """Intersection of two merged lists."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(merged, lo, hi) -> list:
    """The gaps of a merged list inside [lo, hi)."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


# -- per-device quantities over a window ---------------------------------

@dataclass
class Window:
    """The traced window: the first advance() span's start to the last
    one's end, on the host clock the device planes share."""
    start: float
    end: float

    @property
    def length(self):
        return self.end - self.start

    @classmethod
    def of(cls, trace: Trace, span: str):
        mine = [s for s in trace.spans if s[0] == span]
        if not mine:
            return None
        return cls(min(s[1] for s in mine), max(s[2] for s in mine))


def clip(intervals, w: Window) -> list:
    return [(max(s, w.start), min(e, w.end)) for s, e in intervals
            if e > w.start and s < w.end]


def compute(dev: Device, w: Window) -> list:
    return merge(clip([(s, e) for _, s, e in dev.ops], w))


def comm(dev: Device, w: Window) -> list:
    return merge(clip(dev.comm, w))


def busy(dev: Device, w: Window) -> list:
    return merge(compute(dev, w) + comm(dev, w))


def exposed_comm(dev: Device, w: Window) -> float:
    """Collective time with no compute on the same device."""
    c = comm(dev, w)
    return total(c) - total(intersect(c, compute(dev, w)))


def chunk_runs(dev: Device, w: Window) -> list:
    """Executions of the chunk program inside the window, in order: the
    module that takes most of the device's module time."""
    per = defaultdict(float)
    for name, s, e in dev.modules:
        per[name] += e - s
    if not per:
        return []
    top = max(per, key=per.get)
    return sorted((s, e) for name, s, e in dev.modules
                  if name == top and s >= w.start and e <= w.end)


def boundary_idle(dev: Device, w: Window) -> list:
    """Device idle time between the end of each chunk program's execution
    and the start of the next one, one entry per boundary."""
    runs = chunk_runs(dev, w)
    b = busy(dev, w)
    out = []
    for (_, e0), (s1, _) in zip(runs, runs[1:]):
        gap = [(e0, s1)] if s1 > e0 else []
        out.append(total(gap) - total(intersect(gap, b)))
    return out


def idle_gaps(dev: Device, w: Window, spans) -> list:
    """``[(host span, seconds)]`` of every idle gap of the device in the
    window, named by the benchmark's innermost host span that holds the
    gap's middle ("bench.window" when none of its inner spans does)."""
    out = []
    for s, e in complement(busy(dev, w), w.start, w.end):
        mid = (s + e) / 2
        holders = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = (min(holders, key=lambda sp: sp[2] - sp[1])[0] if holders
                else SPAN_PREFIX + "window")
        out.append((name, (e - s) / 1e9))
    return out


def top_ops(devices, w: Window, k: int = 10) -> list:
    """``[(op name, seconds per device)]`` of the ``k`` ops that took most
    device time in the window."""
    per = defaultdict(float)
    for dev in devices:
        for name, s, e in dev.ops:
            if e > w.start and s < w.end:
                per[name] += min(e, w.end) - max(s, w.start)
    rows = sorted(per.items(), key=lambda kv: -kv[1])[:k]
    return [(name, t / 1e9 / len(devices)) for name, t in rows]
