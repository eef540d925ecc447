"""Reduction of one profiler trace (``*.xplane.pb``) to what the
per-layer metrics read: device busy time, time per kernel, the device
operations that took most time, and the idle gaps with what the host
was doing in each.

What a trace holds, as read by hand from a TPU v5e trace of this
benchmark: the device plane ``/device:TPU:<n>`` has a line ``XLA Ops``
whose events are single HLO operations, one after another, named by the
operation's HLO text (``%conv2d_int8.3 = s8[...] custom-call(...)``);
a Pallas kernel is a ``custom-call`` named after the jitted function
that wraps it. Line ``XLA Modules`` holds whole program executions (a
served plan call is one, the small random-key programs around it are
others) and ``Async XLA Ops`` the copies that overlap them; neither
counts toward busy time. A plan call is told from the others by the
kernels it runs, not by its name. The host plane ``/host:CPU`` has one
line per thread; the benchmark's own spans there are named
``bench.*``. Device and host events share one clock, in nanoseconds
from the start of the trace.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "no bench span"
TOP = 10


class Op(NamedTuple):
    base: str           # HLO op name without '%' and its '.N' suffix
    start: float        # ns
    end: float
    custom_call: bool


class Span(NamedTuple):
    name: str
    start: float
    end: float


def op_base(name: str) -> str:
    """``'%conv2d_int8.3 = s8[..] custom-call(..)'`` -> ``'conv2d_int8'``;
    ``'%pad.18.clone = ..'`` -> ``'pad'``."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+|\.clone)+$", "", head)


def load(path: str) -> Tuple[List[List[Op]], List[List[Span]],
                             List[Span]]:
    """(ops per chip, program executions per chip, benchmark host spans)
    from a trace directory or file."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} traces under {path}")
        path = found[0]
    data = ProfileData.from_file(path)
    devices: List[List[Op]] = []
    modules: List[List[Span]] = []
    spans: List[Span] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Op(op_base(ev.name), ev.start_ns,
                               ev.start_ns + ev.duration_ns,
                               "custom-call" in ev.name)
                            for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods += [Span(ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns)
                             for ev in line.events]
            devices.append(sorted(ops, key=lambda o: o.start))
            modules.append(sorted(mods, key=lambda m: m.start))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    return devices, modules, spans


def busy_intervals(ops: List[Op], lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    """The union of operation intervals, clipped to ``[lo, hi]``."""
    merged: List[List[float]] = []
    for op in ops:
        a, b = max(op.start, lo), min(op.end, hi)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


class Summary:
    def __init__(self, devices: List[List[Op]], modules: List[List[Span]],
                 spans: List[Span]):
        if not devices:
            raise ValueError("the trace holds no TPU device plane")
        self.devices = devices
        self.modules = modules
        self.spans = spans
        windows = [s for s in spans if s.name == WINDOW_SPAN]
        if len(windows) != 1:
            raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in trace")
        self.window = (windows[0].start, windows[0].end)

    def busy_seconds(self, lo: float, hi: float) -> float:
        """Seconds with an operation running, averaged over the chips."""
        total = sum(b - a for ops in self.devices
                    for a, b in busy_intervals(ops, lo, hi))
        return total * 1e-9 / len(self.devices)

    def plan_calls(self) -> List[Dict[str, Tuple[float, int]]]:
        """The first chip's program executions that run a custom-call
        kernel, in order, each as {kernel: (device seconds, calls)}. The
        trace starts with the device idle, so the i-th of them is the
        window's i-th dispatch; an execution the trace's end cut off is
        not in the trace."""
        out = []
        ops = [op for op in self.devices[0] if op.custom_call]
        i = 0
        for mod in self.modules[0]:
            while i < len(ops) and ops[i].start < mod.start:
                i += 1
            call: Dict[str, Tuple[float, int]] = {}
            j = i
            while j < len(ops) and ops[j].end <= mod.end:
                s, n = call.get(ops[j].base, (0.0, 0))
                call[ops[j].base] = (s + (ops[j].end - ops[j].start) * 1e-9,
                                     n + 1)
                j += 1
            if call:
                out.append(call)
            i = j
        return out

    def top_ops(self, k: int = TOP) -> List[List]:
        """Device operations inside the window by total time."""
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for ops in self.devices:
            for op in ops:
                a, b = max(op.start, lo), min(op.end, hi)
                if b > a:
                    tot[op.base] = tot.get(op.base, 0.0) + (b - a) * 1e-9
        return [[n, s] for n, s in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def label(self, t: float) -> str:
        """The innermost benchmark span running at ``t``."""
        inside = [s for s in self.spans
                  if s.start <= t <= s.end and s.name != WINDOW_SPAN]
        if not inside:
            return NO_SPAN
        return min(inside, key=lambda s: s.end - s.start).name

    def idle_gaps(self, k: int = TOP) -> List[List]:
        """The longest device-idle gaps inside the window, on the first
        chip, each labelled with the host span at its midpoint."""
        lo, hi = self.window
        busy = busy_intervals(self.devices[0], lo, hi)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.label((a + b) / 2), (b - a) * 1e-9]
                for a, b in gaps[:k]]

    def breakdown(self) -> Dict[str, List[List]]:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}

