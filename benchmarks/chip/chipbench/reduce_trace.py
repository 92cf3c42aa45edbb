"""Profiler trace (``.xplane.pb``) to device events, busy time, idle
gaps and the host span each gap fell in.

The device planes are ``/device:<accelerator>:<n>``; on them the line
``XLA Ops`` holds one event per executed operation, named by its HLO
instruction (``%matmul_tile.505 = bf16[16,151936]... custom-call(...)``:
a Pallas kernel's instruction carries the name its ``pallas_call`` was
given), and ``XLA Modules`` one per executed program
(``jit_step(5167...)``). Host
spans are the ``TraceAnnotation`` events the harness writes on the host
plane. Everything is clipped to the harness's ``bench_window`` span.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[float, float]     # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start: float    # ns
    end: float      # ns


@dataclasses.dataclass
class Summary:
    window: Interval
    ops: List[Event]                       # device ops, first device, clipped
    modules: List[Event]                   # device programs, first device
    spans: List[Event]                     # host spans of the harness
    devices: int                           # device planes seen
    busy_ns: float                         # busy union, averaged over devices

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9


def base_name(name: str) -> str:
    """An op or program name without its instance suffix or the rest of
    its HLO text: ``%fusion.12 = f32[8] fusion(...)`` -> ``fusion``,
    ``jit_step(3)`` -> ``jit_step``."""
    name = name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]
    return re.sub(r"(\.\d+)+$", "", name)


def same_program(name: str, fn_name: str) -> bool:
    """Whether a program event ``name`` is the jit of a function called
    ``fn_name`` (``jit_<lambda>`` appears as ``jit__lambda``)."""
    def key(s):
        return re.sub(r"[^a-z0-9]", "", s.lower())
    return key(base_name(name)) == key("jit_" + fn_name)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(ev: Event, win: Interval) -> Optional[Event]:
    s, e = max(ev.start, win[0]), min(ev.end, win[1])
    if e <= s:
        return None
    return dataclasses.replace(ev, start=s, end=e)


def _events(line) -> List[Event]:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def reduce_planes(planes, span_names: Sequence[str]) -> Summary:
    """Reduce the planes of a ``jax.profiler.ProfileData``."""
    spans, window = [], None
    devices = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in _events(line):
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start, ev.end)
                    elif ev.name in span_names:
                        spans.append(ev)
        elif plane.name.startswith("/device:") and not plane.name.startswith("/device:CUSTOM"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines or MODULES_LINE in lines:
                devices.append((plane.name, lines))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    if not devices:
        raise ValueError("no device plane with XLA ops in the trace")
    devices.sort(key=lambda d: d[0])
    busy = []
    first_ops = first_modules = None
    for _, lines in devices:
        ops = [c for ev in _events(lines[OPS_LINE])
               if (c := _clip(ev, window))] if OPS_LINE in lines else []
        mods = [c for ev in _events(lines[MODULES_LINE])
                if (c := _clip(ev, window))] if MODULES_LINE in lines else []
        busy.append(sum(e - s for s, e in union(
            [(ev.start, ev.end) for ev in (ops or mods)])))
        if first_ops is None:
            first_ops, first_modules = ops, mods
    spans = [c for ev in spans if (c := _clip(ev, window))]
    return Summary(window=window, ops=first_ops, modules=first_modules,
                   spans=spans, devices=len(devices),
                   busy_ns=sum(busy) / len(busy))


def reduce_file(path: str, span_names: Sequence[str]) -> Summary:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, span_names)


def kernel_ns(summary: Summary, kernel: str) -> float:
    """Summed device time of the ops that are the Pallas kernel
    ``kernel`` (by the instruction's own name, not its operands)."""
    return sum(ev.end - ev.start for ev in summary.ops if base_name(ev.name) == kernel)


def program_ns(summary: Summary, fn_name: str) -> Tuple[float, int]:
    """(summed device time, executions) of the programs jitted from a
    function called ``fn_name``."""
    evs = [ev for ev in summary.modules if same_program(ev.name, fn_name)]
    return sum(ev.end - ev.start for ev in evs), len(evs)


def top_ops(summary: Summary, n: int = 10) -> List[list]:
    """The device operations that took most time, by base name."""
    tot: Dict[str, float] = {}
    for ev in summary.ops:
        k = base_name(ev.name)
        tot[k] = tot.get(k, 0.0) + (ev.end - ev.start)
    return [[k, v * 1e-9] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(summary: Summary) -> List[Tuple[str, float]]:
    """Each idle interval of the first device inside the window, as
    (host span it fell in, seconds). The span is the innermost (latest
    started) harness span covering the gap's midpoint, else ``none``."""
    busy = union([(ev.start, ev.end) for ev in (summary.ops or summary.modules)])
    lo, hi = summary.window
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in gaps:
        mid = (s + e) / 2
        cover = [sp for sp in summary.spans if sp.start <= mid <= sp.end]
        label = max(cover, key=lambda sp: sp.start).name if cover else "none"
        out.append((label, (e - s) * 1e-9))
    return out


def idle_by_span(summary: Summary, n: int = 10) -> List[list]:
    """Idle seconds summed by the host span they fell in, largest first."""
    tot: Dict[str, float] = {}
    for label, sec in idle_gaps(summary):
        tot[label] = tot.get(label, 0.0) + sec
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]
