"""From a profiler trace to the numbers the metric readers take.

A trace is loaded into plain ``Event`` rows (plane, line, name, start and
duration in ns, stats), so the reduction below can be checked on a small
recorded excerpt (``bench/tests/data``) without a chip.

On a TPU the device plane is ``/device:TPU:<n>``: its ``XLA Modules`` line
holds one event per execution of a compiled program (named after the
jitted function), its ``XLA Ops`` line one event per operation, Pallas
kernels among them.  Host spans written by the harness
(``jax.profiler.TraceAnnotation``) sit on the host plane's threads, on the
same clock.  An operation's name is its HLO text; a Pallas kernel call is
a ``custom-call`` to ``tpu_custom_call`` and carries no kernel name, so the
program execution that covers it in time says which kernel it is.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."          # the harness's own span names


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object]

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path: str) -> List[Event]:
    """The events the reduction reads: device operations and program
    executions, and the harness's own host spans (a trace of a whole
    window holds about a million operations, so nothing else is kept)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                name = e.name
                if device or name.startswith(HOST_PREFIX):
                    out.append(Event(plane.name, line.name, name,
                                     float(e.start_ns),
                                     float(e.duration_ns), {}))
    return out


def within(events: Iterable[Event], t0_ns: float, t1_ns: float,
           clip: bool = False) -> List[Event]:
    """Events that start inside [t0, t1); with ``clip``, every event that
    overlaps it, cut to it."""
    if not clip:
        return [e for e in events if t0_ns <= e.start_ns < t1_ns]
    out = []
    for e in events:
        s, t = max(e.start_ns, t0_ns), min(e.end_ns, t1_ns)
        if t > s:
            out.append(e if (s, t) == (e.start_ns, e.end_ns) else
                       dataclasses.replace(e, start_ns=s, dur_ns=t - s))
    return out


def device_ops(events: Iterable[Event], plane: Optional[str] = None
               ) -> List[Event]:
    return [e for e in events if e.plane.startswith(DEVICE_PLANE)
            and e.line == OPS_LINE and (plane is None or e.plane == plane)
            and e.dur_ns > 0]


def modules(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if e.plane.startswith(DEVICE_PLANE)
            and e.line == MODULES_LINE and e.dur_ns > 0]


def host_spans(events: Iterable[Event]) -> List[Event]:
    return [e for e in events if not e.plane.startswith(DEVICE_PLANE)
            and e.name.startswith(HOST_PREFIX)]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def busy_s(ops: List[Event]) -> float:
    """Seconds in which at least one operation ran (per plane, averaged)."""
    planes = sorted({e.plane for e in ops})
    if not planes:
        return 0.0
    tot = 0.0
    for p in planes:
        tot += sum(t - s for s, t in union(
            (e.start_ns, e.end_ns) for e in ops if e.plane == p))
    return tot / len(planes) * 1e-9


def short_name(e: Event) -> str:
    """An operation's HLO name without its text (``%fusion.12 = ...`` ->
    ``fusion.12``), a program's without its id (``jit__f(123)`` ->
    ``jit__f``)."""
    return e.name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def program_of(ops: List[Event], mods: List[Event]) -> List[str]:
    """For each operation, the short name of the program execution that
    covers it in time on its plane ('' where none does)."""
    out = [""] * len(ops)
    by_plane: Dict[str, list] = {}
    for m in mods:
        by_plane.setdefault(m.plane, []).append(m)
    order = sorted(range(len(ops)), key=lambda i: (ops[i].plane,
                                                    ops[i].start_ns))
    j, plane, ms = 0, None, []
    for i in order:
        e = ops[i]
        if e.plane != plane:
            plane = e.plane
            ms = sorted(by_plane.get(plane, []), key=lambda m: m.start_ns)
            j = 0
        while j < len(ms) and ms[j].end_ns <= e.start_ns:
            j += 1
        if j < len(ms) and ms[j].start_ns <= e.start_ns:
            out[i] = short_name(ms[j])
    return out


def top_ops(ops: List[Event], mods: List[Event], n: int = 10) -> List[list]:
    """Device operations that took most time, summed by program and
    operation name (seconds, averaged over planes)."""
    acc: Dict[str, float] = {}
    for e, prog in zip(ops, program_of(ops, mods)):
        key = f"{prog or '-'}/{short_name(e)}"
        acc[key] = acc.get(key, 0.0) + e.dur_ns
    planes = max(1, len({e.plane for e in ops}))
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9 / planes] for k, v in top]


def idle_gaps(ops: List[Event], spans: List[Event], t0_ns: float,
              t1_ns: float, n: int = 10) -> List[list]:
    """Device idle time inside [t0, t1] on the first device plane, summed by
    what the host was doing (the innermost harness span covering each
    idle instant; ``no span`` where none does)."""
    planes = sorted({e.plane for e in ops})
    if not planes:
        return []
    busy = union((e.start_ns, e.end_ns) for e in ops if e.plane == planes[0])
    gaps, cur = [], t0_ns
    for s, t in busy:
        if s > cur:
            gaps.append((cur, min(s, t1_ns)))
        cur = max(cur, t)
        if cur >= t1_ns:
            break
    if cur < t1_ns:
        gaps.append((cur, t1_ns))
    segs = _span_segments(spans)
    acc: Dict[str, float] = {}
    j = 0
    for gs, gt in gaps:
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        cur = gs
        while cur < gt:
            if k < len(segs) and segs[k][0] < gt:
                a, b, name = segs[k]
                if a > cur:                 # before this segment: no span
                    acc["no span"] = acc.get("no span", 0.0) + (a - cur)
                    cur = a
                end = min(b, gt)
                acc[name] = acc.get(name, 0.0) + (end - cur)
                cur = end
                if b <= gt:
                    k += 1
            else:
                acc["no span"] = acc.get("no span", 0.0) + (gt - cur)
                cur = gt
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def _span_segments(spans: List[Event]) -> List[Tuple[float, float, str]]:
    """Disjoint (start, end, name) pieces of the host timeline, each named
    after the innermost (shortest) harness span covering it."""
    marks = sorted([(e.start_ns, 1, i) for i, e in enumerate(spans)]
                   + [(e.end_ns, 0, i) for i, e in enumerate(spans)])
    active: Dict[int, Event] = {}
    out: List[Tuple[float, float, str]] = []
    prev = None
    for t, kind, i in marks:
        if prev is not None and t > prev and active:
            name = min(active.values(), key=lambda e: e.dur_ns).name
            out.append((prev, t, name))
        if kind == 1:
            active[i] = spans[i]
        else:
            active.pop(i, None)
        prev = t
    return out


KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def kernel_time_s(ops: List[Event], mods: List[Event], program: str
                  ) -> Tuple[float, int]:
    """Summed device time (seconds, averaged over planes) and count of the
    Pallas kernel calls (``tpu_custom_call`` operations) that run inside
    executions of the programs whose name contains ``program``.  The
    trace names a kernel call by its HLO text only, so the program that
    holds it tells the kernels apart."""
    hit = [e for e, prog in zip(ops, program_of(ops, mods))
           if program in prog and KERNEL_MARK in e.name]
    planes = max(1, len({e.plane for e in hit}))
    return sum(e.dur_ns for e in hit) * 1e-9 / planes, len(hit) // planes


def module_times_s(mods: List[Event], needle: str) -> List[float]:
    """Durations (seconds) of the program executions whose name contains
    ``needle``, on the first device plane."""
    planes = sorted({e.plane for e in mods})
    if not planes:
        return []
    return [e.dur_ns * 1e-9 for e in mods
            if e.plane == planes[0] and needle in e.name]


def host_window_ns(spans: List[Event]) -> Optional[Tuple[float, float]]:
    """The traced window on the trace's clock: the harness's window span."""
    w = [e for e in spans if e.name == HOST_PREFIX + "traced_window"]
    return (w[0].start_ns, w[0].end_ns) if w else None
