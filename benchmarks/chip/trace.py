"""Reduction of a profiler trace to device time, idle gaps and kernel time.

``capture`` runs a function under ``jax.profiler`` and returns the trace of
the window it ran in, as plain events. The window is the benchmark's own
``bench/window`` span on the host. Device operations are the events of the
TPU planes' ``XLA Ops`` lines; busy time is the union of their intervals
inside the window, averaged over the chips that have any. An idle gap is
an interval of the window in which no operation ran on a chip; it is named
by the innermost host event that covers its midpoint, on the thread that
ran the window.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import re
import shutil
import tempfile
from typing import Dict, Iterator, List, Optional, Tuple

WINDOW = "bench/window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    name: str
    start: int               # ns
    end: int                 # ns


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    devices: Dict[str, List[Event]]   # plane name -> its ops in the window
    host: List[Event]                 # host events on the window's thread

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Device busy seconds, averaged over the chips that ran ops."""
        busy = [sum(b - a for a, b in _union(ops))
                for ops in self.devices.values() if ops]
        return sum(busy) * 1e-9 / len(busy) if busy else 0.0

    def idle_share(self) -> Optional[float]:
        if not any(self.devices.values()) or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def ops(self) -> Iterator[Event]:
        for ops in self.devices.values():
            yield from ops

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        """Device self time per operation, largest first, in seconds summed
        over chips. The trace nests operations (a loop holds its body), so
        each is charged its own time less that of the operations inside
        it."""
        total: Dict[str, int] = collections.Counter()
        for ops in self.devices.values():
            for op, ns in _self_times(ops):
                total[short_name(op.name)] += ns
        return [(name, ns * 1e-9) for name, ns in total.most_common(k)]

    def idle_gaps(self, k: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of the busiest chip, each named by what
        the host was doing, largest first, in seconds."""
        ops = max(self.devices.values(), key=len, default=[])
        gaps = []
        cursor = self.window[0]
        for a, b in _union(ops) + [(self.window[1], self.window[1])]:
            if a > cursor:
                gaps.append((cursor, a))
            cursor = max(cursor, b)
        gaps.sort(key=lambda g: g[0] - g[1])
        return [(self.host_at((a + b) // 2), (b - a) * 1e-9)
                for a, b in gaps[:k]]

    def host_at(self, t: int) -> str:
        inner = None
        for ev in self.host:
            if ev.start <= t <= ev.end and (
                    inner is None or ev.end - ev.start < inner.end - inner.start):
                inner = ev
        return inner.name if inner is not None else "outside any span"


def _self_times(events: List[Event]) -> List[Tuple[Event, int]]:
    """Each event with its duration less that of the events nested in it."""
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    own = [e.end - e.start for e in ordered]
    stack: List[int] = []
    for i, ev in enumerate(ordered):
        while stack and ordered[stack[-1]].end <= ev.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= ev.end - ev.start
        stack.append(i)
    return list(zip(ordered, own))


def short_name(hlo: str) -> str:
    """``%fusion.286 = s32[33554432]{...} fusion(...)`` ->
    ``fusion.286 fusion s32[33554432]``: the instruction, its opcode and
    its (first) result shape."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        result, rest = rest[1:i], rest[i + 1:]
    else:
        result, _, rest = rest.partition(" ")
    opcode = rest.strip().partition("(")[0]
    shape = re.match(r"\s*(\w+\[[0-9,]*\])", result)
    return " ".join(x for x in (name.lstrip("%"), opcode,
                                shape.group(1) if shape else "") if x)


def _union(events: List[Event]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for ev in sorted(events, key=lambda e: e.start):
        if out and ev.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], ev.end)
        else:
            out.append([ev.start, ev.end])
    return [(a, b) for a, b in out]


def _clip(ev: Event, lo: int, hi: int) -> Optional[Event]:
    start, end = max(ev.start, lo), min(ev.end, hi)
    if end <= start:
        return None
    return Event(ev.name, start, end)


def _events(line) -> Iterator[Event]:
    for e in line.events:
        start = int(e.start_ns)
        yield Event(e.name, start, start + int(e.duration_ns))


def reduce(profile, span: str = WINDOW) -> Trace:
    """A ``jax.profiler.ProfileData`` to the events inside the host span
    named ``span``."""
    window = None
    host_lines = []
    devices: Dict[str, List[Event]] = {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line))
            devices[plane.name] = ops
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                events = list(_events(line))
                for ev in events:
                    if ev.name == span:
                        window = (ev.start, ev.end)
                        host_lines.append(events)
    if window is None:
        raise ValueError(f"the trace has no {span!r} span")
    lo, hi = window
    clipped = {name: [c for c in (_clip(e, lo, hi) for e in ops) if c]
               for name, ops in devices.items()}
    host = [c for line in host_lines for c in (_clip(e, lo, hi) for e in line)
            if c is not None and c.name != span]
    return Trace(window, clipped, host)


def load(path: str) -> Trace:
    import jax

    return reduce(jax.profiler.ProfileData.from_file(path))


@contextlib.contextmanager
def capture(holder: list):
    """Trace the body; append its reduced ``Trace`` to ``holder``."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    tmp = tempfile.mkdtemp(prefix="chip-trace-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        holder.append(load(files[0]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
