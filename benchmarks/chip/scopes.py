"""A traced window's device time and idle gaps, charged to the program's
own names (``repro.pregel.tracing``).

Device time: each operation's self time (``trace._self_times``) goes to
the scope path that the program's scope table gives its short name
(``trace.short_name``); an operation the table does not know, or knows
as ambiguous, has path "". Seconds are averaged over the chips that ran
operations, as ``Trace.busy_s`` is, so the charges add up to the busy
time. Time is unscoped where its path names nothing inside the loop: ""
or a bare ``superstep``. A channel, a phase, the program's ``compute``
or the loop's ``vote`` owns the rest.

Idle gaps: each gap of the busiest chip goes to the innermost program
span (``job/*``, ``serve/*``) that covers its midpoint, or to none.

A program without ``repro.pregel.tracing`` names nothing: every reader
then returns None.
"""
from __future__ import annotations

import collections
from typing import Callable, Dict, List, Optional, Tuple

import trace as chiptrace

PHASES = ("route", "exchange", "combine")
#: the compiled loop's own scope: time under it alone has no owner
LOOP = "superstep"
#: composed S-V's pointer-jumping channels: the full jump to a star, and
#: the root test's lookups
JUMP = ("sv/jump", "sv/pointer")
#: the program's host spans, by prefix
PROGRAM_SPANS = ("job/", "serve/")


def _tracing():
    try:
        from repro.pregel import tracing
    except ImportError:
        return None
    return tracing


def names_spans() -> bool:
    """Whether the running program names its host spans."""
    return _tracing() is not None


def table() -> Optional[Dict[str, str]]:
    """The running program's scope table (``tracing.scope_table``), or
    None where it has none."""
    tracing = _tracing()
    return None if tracing is None else tracing.scope_table()


def charge(trace: chiptrace.Trace, table: Dict[str, str]) -> Dict[str, float]:
    """Scope path -> device self seconds, averaged over the chips that ran
    operations; unscoped time under ""."""
    total: Dict[str, int] = collections.Counter()
    chips = 0
    for ops in trace.devices.values():
        if not ops:
            continue
        chips += 1
        for op, ns in chiptrace._self_times(ops):
            total[table.get(chiptrace.short_name(op.name), "")] += ns
    return {path: ns * 1e-9 / chips for path, ns in total.items()}


def phase(path: str) -> Optional[str]:
    """The innermost phase a scope path lies under, if any."""
    for part in reversed(path.split("/")):
        if part in PHASES:
            return part
    return None


def under(path: str, channel: str) -> bool:
    """Whether a scope path lies under a channel scope."""
    return f"/{channel}/" in f"/{path}/"


def seconds(charges: Dict[str, float], pick: Callable[[str], bool]) -> float:
    return sum(s for path, s in charges.items() if path and pick(path))


def gaps(trace: chiptrace.Trace) -> List[Tuple[int, int]]:
    """Every idle interval of the busiest chip inside the window."""
    ops = max(trace.devices.values(), key=len, default=[])
    out = []
    cursor = trace.window[0]
    for a, b in chiptrace._union(ops) + [(trace.window[1], trace.window[1])]:
        if a > cursor:
            out.append((cursor, a))
        cursor = max(cursor, b)
    return out


def span_at(trace: chiptrace.Trace, t: int) -> Optional[str]:
    """The innermost program span covering ``t``, or None."""
    inner = None
    for ev in trace.host:
        if ev.name.startswith(PROGRAM_SPANS) and ev.start <= t <= ev.end \
                and (inner is None
                     or ev.end - ev.start < inner.end - inner.start):
            inner = ev
    return inner.name if inner is not None else None


def idle_by_span(trace: chiptrace.Trace) -> Dict[Optional[str], float]:
    """Idle seconds of the busiest chip per owning program span."""
    out: Dict[Optional[str], float] = collections.Counter()
    for a, b in gaps(trace):
        out[span_at(trace, (a + b) // 2)] += (b - a) * 1e-9
    return out


# the trace last charged, and its charges: a run's readers share them
_last: list = [None, None]


def run_charges(run) -> Optional[Dict[str, float]]:
    """The run's trace charged to the running program's scopes, or None
    where there is no trace or the program names no scopes."""
    found = table() if run.trace is not None else None
    if found is None:
        return None
    if _last[0] is not run.trace:
        _last[:] = [run.trace, charge(run.trace, found)]
    return _last[1]


def per_job(run, pick: Callable[[str], bool]) -> Optional[float]:
    """Device seconds a job under the scope paths ``pick`` accepts."""
    charges = run_charges(run) if run.window.jobs else None
    if charges is None:
        return None
    return seconds(charges, pick) / len(run.window.jobs)


def outside_jump(name: str) -> Callable[[str], bool]:
    """Paths under phase ``name`` outside the pointer-jumping channels,
    so that the phase metrics and ``jump_s.job`` never count a second
    twice."""
    return lambda path: phase(path) == name and not any(
        under(path, ch) for ch in JUMP)


def unscoped(path: str) -> bool:
    """Whether a scope path names no owner inside the loop."""
    return path in ("", LOOP)


def unscoped_share(run) -> Optional[float]:
    """Percent of the device's busy time that no owner inside the loop
    claims (:func:`unscoped`)."""
    charges = run_charges(run)
    busy = run.trace.busy_s() if charges is not None else 0.0
    if busy <= 0:
        return None
    return 100.0 * sum(s for path, s in charges.items()
                       if unscoped(path)) / busy
