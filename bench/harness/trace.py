"""Reduce a profiler trace (``.xplane.pb``) to what the layer metrics read.

Planes named ``/device:<KIND>:<n>`` hold the device timelines; on a TPU the
line ``XLA Ops`` has one event per operation the device ran. The host
plane ``/host:CPU`` holds the runtime's own events, the harness's
``TraceAnnotation`` spans (``WINDOW``, and ``STEP`` around each
``sim.step()``) and, where the Python tracer was on, one event per Python
call; the program's own spans (``engine:rebin``, ...) are added from its
tracer. Every device interval is clipped to the window. Only the events of
the harness's own thread are kept: the runtime's worker threads run
thousands of short transposes that overlap every gap, and would hide what
the thread that drives the device was doing.

A TPU names each operation event by its whole HLO instruction
(``%fusion.12 = f32[...] fusion(...), ...``); an operation is kept under
``<module>/<instruction>``, the module being the program (``XLA Modules``
line) it ran in, and counts as a collective when its instruction is one.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
STEP = "bench_step"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
MAIN_LINE = "main/"
COLLECTIVE = re.compile(
    r"(all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all)"
    r"(-start|-done)?(\.\d+)?")

Interval = Tuple[float, float]


@dataclass
class DeviceTimeline:
    name: str
    ops: List[Tuple[float, float, str]]      # (start_ns, end_ns, op name)
    busy: List[Interval] = field(default_factory=list)

    @property
    def busy_ns(self) -> float:
        return sum(b - a for a, b in self.busy)

    @property
    def collective_ns(self) -> float:
        return sum(b - a for a, b, name in self.ops
                   if COLLECTIVE.fullmatch(name.rsplit("/", 1)[-1]))


@dataclass
class TraceSummary:
    window: Interval                          # ns, on the trace's clock
    devices: List[DeviceTimeline]
    host: List[Tuple[float, float, str]]      # host events inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) * 1e-9

    def collective_s(self) -> float:
        return (sum(d.collective_ns for d in self.devices)
                / len(self.devices) * 1e-9)

    def top_ops(self, k: int = 10) -> List[List]:
        """Operations by device seconds, averaged over the devices."""
        tot: Dict[str, float] = collections.Counter()
        for d in self.devices:
            for a, b, name in d.ops:
                tot[name] += (b - a) * 1e-9 / len(self.devices)
        return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])
                [:k]]

    def add_host_spans(self, spans: Sequence[Tuple[str, float, float]],
                       window_opened: float) -> None:
        """Put the program's own spans ``(name, t0, t1)``, in
        ``time.perf_counter`` seconds, on the trace's clock, given the
        ``perf_counter`` reading taken as the window span opened; they
        are named ``engine:<name>``."""
        w0 = self.window[0]
        for name, a, b in spans:
            self.host.append((w0 + (a - window_opened) * 1e9,
                              w0 + (b - window_opened) * 1e9,
                              f"engine:{name}"))

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Idle seconds of the first device, by the innermost host event
        that was open at the middle of each gap."""
        if not self.devices:
            return []
        gaps, t = [], self.window[0]
        for a, b in self.devices[0].busy + [(self.window[1],) * 2]:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        mids = [0.5 * (a + b) for a, b in gaps]
        inner: List[Tuple[float, str]] = [(float("inf"), "(no host event)")
                                          for _ in gaps]
        for s, e, name in self.host:
            lo = bisect.bisect_left(mids, s)
            hi = bisect.bisect_left(mids, e)
            for i in range(lo, hi):
                inner[i] = min(inner[i], (e - s, name))
        tot: Dict[str, float] = collections.Counter()
        for (a, b), (_, name) in zip(gaps, inner):
            tot[name] += (b - a) * 1e-9
        return [[n, s] for n, s in sorted(tot.items(), key=lambda x: -x[1])
                [:k]]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def instruction(op_event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` → ``fusion.12``."""
    return op_event_name.split(" = ", 1)[0].lstrip("%")


def module_name(module_event_name: str) -> str:
    """``jit_cycle(7639791831725600669)`` → ``jit_cycle``."""
    return module_event_name.split("(", 1)[0]


def _device_index(name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:([A-Z]+):(\d+)", name)
    return None if m is None or m.group(1) == "CPU" else int(m.group(2))


def summarize(path: str, ndevices: Optional[int] = None) -> TraceSummary:
    """Read one ``.xplane.pb``: the window, each device's operations in it
    (the first ``ndevices`` devices, or all), and the host events in it."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_events, dev_planes = [], []
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            # the harness's thread: the line its spans are on, and the
            # runtime's own line of the main thread
            lines = [line for line in plane.lines
                     if line.name.startswith(MAIN_LINE)
                     or any(e.name == WINDOW for e in line.events)]
            for line in lines:
                host_events.extend((e.start_ns, e.end_ns, e.name)
                                   for e in line.events)
        elif _device_index(plane.name) is not None:
            dev_planes.append(plane)
    windows = [(s, e) for s, e, name in host_events if name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span in {path}, found "
                         f"{len(windows)}")
    w0, w1 = windows[0]
    dev_planes.sort(key=lambda p: _device_index(p.name))
    if ndevices is not None:
        dev_planes = dev_planes[:ndevices]
    devices = []
    for plane in dev_planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted((e.start_ns, e.end_ns, module_name(e.name))
                         for e in lines.get(MODULE_LINE, []))
        starts = [m[0] for m in modules]
        ops = []
        for e in lines.get(OP_LINE, []):
            a, b = max(e.start_ns, w0), min(e.end_ns, w1)
            if b <= a:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            mod = modules[i][2] if i >= 0 and e.start_ns < modules[i][1] \
                else "(no module)"
            ops.append((a, b, f"{mod}/{instruction(e.name)}"))
        devices.append(DeviceTimeline(plane.name, ops,
                                      union([(a, b) for a, b, _ in ops])))
    host = [(s, e, n) for s, e, n in host_events
            if e > w0 and s < w1 and n != WINDOW]
    return TraceSummary((w0, w1), devices, host)
