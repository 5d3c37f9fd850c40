"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device planes are those named ``/device:TPU:<i>``.  On each, the line
``XLA Ops`` holds one event per operation run and ``XLA Modules`` one per
program run (a jitted function's module, named ``jit_<function>``).  Host
planes carry the harness's own spans (``jax.profiler.TraceAnnotation``),
all named ``bench.<what>``; the span ``bench.window`` marks the traced
window.

From these:
  * busy: the union of the operations' intervals inside the window;
  * program time: the summed duration and the count of the module events
    whose name matches a pattern;
  * top operations by summed time;
  * idle gaps: the intervals of the window no operation covers, each named
    after the harness span that overlaps it most (what the host was doing).
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

Interval = Tuple[int, int]          # [start_ns, end_ns)


@dataclasses.dataclass
class DeviceEvents:
    ops: List[Tuple[str, int, int]]        # (name, start_ns, end_ns)
    modules: List[Tuple[str, int, int]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                          # averaged over the devices
    devices: int
    modules: Dict[str, Tuple[float, int]]  # name -> (seconds, count), dev 0
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def program(self, pattern: str) -> Tuple[float, int]:
        """Summed device seconds and run count of the programs whose module
        name matches ``pattern`` (``re.match``)."""
        rx = re.compile(pattern)
        sec, n = 0.0, 0
        for name, (s, c) in self.modules.items():
            if rx.match(name):
                sec += s
                n += c
        return sec, n

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _module_name(name: str) -> str:
    # "jit_step(123)" and "jit_step.1" both name the program "jit_step".
    return re.split(r"[(.]", name, maxsplit=1)[0]


def reduce(devices: Dict[int, DeviceEvents],
           spans: Sequence[Tuple[str, int, int]],
           window: Interval, top: int = 10) -> Summary:
    """Numbers of the window ``window`` from the device events and the
    harness's host spans ``(name, start_ns, end_ns)``."""
    lo, hi = window
    busy_total = 0
    busy0: List[Interval] = []
    for i in sorted(devices):
        b = union(clip([(s, e) for _, s, e in devices[i].ops], lo, hi))
        busy_total += sum(e - s for s, e in b)
        if not busy0:
            busy0 = b
    n_dev = max(1, len(devices))
    first = devices[min(devices)] if devices else DeviceEvents([], [])

    modules: Dict[str, List[float]] = {}
    for name, s, e in first.modules:
        if lo <= s < hi:
            m = modules.setdefault(_module_name(name), [0.0, 0])
            m[0] += (e - s) * 1e-9
            m[1] += 1
    ops: Dict[str, float] = {}
    for name, s, e in first.ops:
        if lo <= s < hi:
            ops[name] = ops.get(name, 0.0) + (e - s) * 1e-9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]

    host = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    named = []
    for gs, ge in gaps(busy0, lo, hi):
        best, label = 0, "none"
        for n, s, e in host:
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, label = ov, n
        named.append((label, (ge - gs) * 1e-9))
    named.sort(key=lambda kv: -kv[1])
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total * 1e-9 / n_dev, devices=len(devices),
                   modules={k: (v[0], int(v[1])) for k, v in modules.items()},
                   top_ops=top_ops, idle_gaps=named[:top])


def read_profile(profile, chips: int) -> Summary:
    """Reduce a ``jax.profiler.ProfileData`` over its first ``chips``
    device planes.  Raises ValueError where the window span is missing."""
    devices: Dict[int, DeviceEvents] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            ev = DeviceEvents([], [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ev.ops = [(e.name, int(e.start_ns), int(e.end_ns))
                              for e in line.events]
                elif line.name == MODULES_LINE:
                    ev.modules = [(e.name, int(e.start_ns), int(e.end_ns))
                                  for e in line.events]
            devices[int(m.group(1))] = ev
        elif not m:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.end_ns)))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return reduce(devices, spans, max(windows, key=lambda w: w[1] - w[0]))


def find_xplane(logdir: Path) -> Optional[Path]:
    found = sorted(Path(logdir).rglob("*.xplane.pb"))
    return found[-1] if found else None

