"""Names of the engine's own work, on the device and on the host.

One vocabulary, two carriers, and no switch:

  - **Device scopes** (``jax.named_scope``) name the instructions a
    compiled loop runs. They are metadata only: the compiled program is
    the same instruction for instruction, and a profiler trace of it can
    be read per name. A channel call runs under its full stat key
    (``ChannelContext.full_name``: ``sv/jump``, ``sv/pointer/request``,
    ``propagation`` ...), so device time and ``bytes_by_channel`` line up
    per channel. Inside a channel the work splits into :data:`PHASES`:
    ``route`` (owners, keys, bucket ranks, slots, pack, dedup),
    ``exchange`` (each collective and the unpack after it) and
    ``combine`` (``kops.segment_combine``, the kernel and the
    reference). The runtime puts every instruction of a compiled loop
    under :data:`SUPERSTEP`, the program's step function under
    :data:`COMPUTE` inside it (so a channel's path reads
    ``superstep/compute/sv/jump/route``), and the halt vote and stat
    reductions under :data:`VOTE` (``superstep/vote``).
  - **Host spans** (``jax.profiler.TraceAnnotation``) name what the host
    is doing between device calls: :data:`JOB_SPANS` in ``Engine.run``,
    :data:`SERVE_SPANS` in the serve loop, :data:`COMPILE` around lower
    plus compile, :data:`PLACE` around putting a graph on its devices.
    They are TraceMe events, on the profiler's clock, and cost only the
    profiler's inactive check when no trace runs.

:func:`scope_table` maps every instruction of the executables the engine
compiled (or loaded from the persistent cache) to its scope path, read
from the optimized HLO's ``op_name`` metadata; profiler op events name an
instruction by its HLO text only, so this table is how a trace's device
time gets its owners. It is built when first asked, never on the run
path.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import inspect
import re
from collections import deque
from typing import Callable, Dict, Iterator, List, Optional

import jax
from jax.profiler import TraceAnnotation

#: the phases of a channel call, innermost wins where they nest
ROUTE, EXCHANGE, COMBINE = PHASES = ("route", "exchange", "combine")
#: a compiled superstep loop; inside it the program's own step function
#: (every channel call nests in it), and the halt vote and stat reductions
SUPERSTEP, COMPUTE, VOTE = "superstep", "compute", "vote"
SCOPES = PHASES + (SUPERSTEP, COMPUTE, VOTE)

#: host spans of one ``Engine.run`` job, in the order they happen
JOB_SPANS = ("job/init", "job/lookup", "job/dispatch", "job/wait",
             "job/readback", "job/extract")
#: host spans of one serving boundary, in the order they happen
SERVE_SPANS = ("serve/admit", "serve/dispatch", "serve/wait",
               "serve/readback", "serve/account", "serve/harvest")
#: host span around lower plus compile of a superstep loop
COMPILE = "engine/compile"
#: host span around putting a graph on its devices (``pgraph.place``)
PLACE = "engine/place"
SPANS = frozenset(JOB_SPANS + SERVE_SPANS + (COMPILE, PLACE))

# The channel scope open at trace time: a channel called inside another
# channel's scope is the outer one's work (its traffic folds into the
# outer key), so it opens no scope of its own unless its name extends the
# outer one (``sv/pointer`` -> ``sv/pointer/request``).
_open_channel: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("repro_channel_scope", default=None)


@contextlib.contextmanager
def channel(full_name: str) -> Iterator[None]:
    """Device scope of one channel call, named by its full stat key."""
    outer = _open_channel.get()
    if outer is None:
        name = full_name
    elif full_name.startswith(outer + "/"):
        name = full_name[len(outer) + 1:]
    else:
        yield
        return
    token = _open_channel.set(full_name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        _open_channel.reset(token)


def channel_call(fn: Callable) -> Callable:
    """Decorator for a channel's entry point ``fn(ctx, ..., name=...)``:
    each call runs under :func:`channel` of ``ctx.full_name(name)``."""
    signature = inspect.signature(fn)

    @functools.wraps(fn)
    def inner(*args, **kw):
        bound = signature.bind(*args, **kw)
        bound.apply_defaults()
        ctx, name = bound.arguments["ctx"], bound.arguments["name"]
        with channel(ctx.full_name(name)):
            return fn(*args, **kw)
    return inner


def scope(name: str):
    """Device scope of one of :data:`SCOPES`; a context manager, or a
    decorator that runs the whole function under it."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r} (one of {SCOPES})")
    return jax.named_scope(name)


def span(name: str) -> TraceAnnotation:
    """Host span (one of :data:`SPANS`): a TraceMe event on the
    profiler's clock."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}")
    return TraceAnnotation(name)


# ---------------------------------------------------------------------------
# op_name -> scope path
# ---------------------------------------------------------------------------

# name-stack entries JAX adds for its own control flow
_CONTROL = re.compile(r"^(while|body|cond|body_pred|scan|checkpoint|remat|"
                      r"shard_map|closed_call|branch_\d+_fun|"
                      r"custom_vmap_call|pallas_call)$")
# transformation wrappers around the scope entered inside them
_WRAPPER = re.compile(r"^(vmap|pmap|shard_map|jvp|transpose|remat|"
                      r"checkpoint|custom_vmap)\((.*)\)$")
# a traced function's name, not a scope
_FUNCTION = re.compile(r"^p?jit\(.*\)$")


def _split(path: str) -> List[str]:
    """``a/vmap(b/c)/d`` -> ``["a", "vmap(b/c)", "d"]``."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(path[start:i])
            start = i + 1
    parts.append(path[start:])
    return parts


def _scopes(parts: List[str]) -> List[str]:
    out: List[str] = []
    for part in parts:
        wrapped = _WRAPPER.match(part)
        if wrapped:
            out.extend(_scopes(_split(wrapped.group(2))))
        elif part and not _FUNCTION.match(part) \
                and not _CONTROL.match(part):
            out.append(part)
    return out


def scope_path(op_name: str) -> str:
    """The program's scope path of an HLO ``op_name``: JAX's wrappers
    (``jit(...)``, ``while/body``, ``vmap(...)`` ...) and the trailing
    primitive stripped. ``jit(loop)/while/body/vmap(sv/jump)/add`` ->
    ``sv/jump``; "" where the op was emitted under no program scope. An
    instruction the compiler merged from several (``a;b``) gets the
    scopes they share."""
    return "/".join(_shared([_scopes(_split(one)[:-1])
                             for one in op_name.split(";")]))


# ---------------------------------------------------------------------------
# executables -> scope table
# ---------------------------------------------------------------------------

#: how many of the latest executables :func:`scope_table` reads. They are
#: held here, so a table can still be read after their owner dropped
#: them (a benchmark reads its trace after the engine is gone).
KEEP = 16

_compiled: deque = deque(maxlen=KEEP)      # [executable, table or None]


def register(compiled) -> None:
    """Record an executable (a ``jax.stages.Compiled``) for
    :func:`scope_table`. Costs a reference; nothing is read until a table
    is asked for."""
    _compiled.append([compiled, None])


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%\S+ = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")
_CALLED = re.compile(r"(?:body|condition|to_apply|branch_computations)="
                     r"(\{[^}]*\}|%[\w.\-]+)")
# instructions that only move values between computations: they take no
# part in another's work, so no scope is inferred through them
_PLUMBING = frozenset({"parameter", "tuple", "get-tuple-element",
                       "constant", "iota", "while", "conditional", "call",
                       "after-all"})


def _opcode(short: str) -> str:
    parts = short.split()
    return parts[1] if len(parts) > 1 else ""


def short_name(hlo: str) -> str:
    """``%fusion.286 = s32[33554432]{...} fusion(...)`` ->
    ``fusion.286 fusion s32[33554432]``: the instruction, its opcode and
    its first result shape, the form a trace's op is known by."""
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


def _shared(paths: List[List[str]]) -> List[str]:
    """The leading scopes every path has."""
    shared = paths[0]
    for other in paths[1:]:
        n = 0
        while n < min(len(shared), len(other)) and shared[n] == other[n]:
            n += 1
        shared = shared[:n]
    return shared


def table_of(hlo_text: str) -> Dict[str, str]:
    """Short name -> scope path of every instruction of an optimized HLO
    module's text ("" where none is known).

    The compiler drops the metadata of some instructions it makes (a
    scatter it rewrites into a sort and a sorted scatter, the fusion
    around it, a copy it inserts). Such an instruction did the work of the
    code that reads its result: it takes the scopes shared by the
    instructions that read it (their own, or inferred so from their
    readers), unless the scopes shared by the instructions it reads
    (inferred the other way) only add to those. Tuples, parameters and
    other plumbing pass no scope on; a tuple element passes its maker's.
    What is still unknown takes the scope of the loop or branch that runs
    it."""
    key: Dict[str, str] = {}
    scope: Dict[str, List[str]] = {}
    reads: Dict[str, List[str]] = {}
    home: Dict[str, str] = {}                 # instruction -> computation
    callers: Dict[str, List[str]] = {}        # computation -> instructions
    computation = ""
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head is not None:
            computation = head.group(1)
            continue
        instr = _INSTR.match(line)
        if instr is None:
            continue
        name, _, rest = instr.group(1).partition(" = ")
        name = name.lstrip("%")
        key[name] = short_name(instr.group(1))
        home[name] = computation
        for called in _CALLED.findall(rest):
            for comp in _REF.findall(called):
                callers.setdefault(comp, []).append(name)
        reads[name] = _REF.findall(rest)
        op = _OP_NAME.search(line)
        if op:
            scope[name] = scope_path(op.group(1)).split("/")

    def plumbing(name: str) -> bool:
        opcode = _opcode(key[name])
        if opcode == "get-tuple-element":
            return plumbing(reads[name][0]) if reads[name][0] in key \
                else True
        return opcode in _PLUMBING

    work = [n for n in key if not plumbing(n)]     # in program order
    operands = {n: [r for r in reads[n] if r in key and not plumbing(r)]
                for n in work}
    users: Dict[str, List[str]] = {n: [] for n in work}
    for name in work:
        for ref in operands[name]:
            users[ref].append(name)

    def spread(order: List[str], source: Dict[str, List[str]]):
        """Each bare instruction, in ``order``, takes the scopes shared by
        its ``source`` neighbours' own or spread scopes."""
        got: Dict[str, List[str]] = {}
        for name in order:
            if name in scope:
                continue
            known = [scope.get(n) or got.get(n) for n in source[name]]
            known = [p for p in known if p and p != [""]]
            if known:
                got[name] = _shared(known) or [""]
        return got

    # an operand comes before its readers in a computation's text
    from_readers = spread(work[::-1], users)
    from_reads = spread(work, operands)
    for name in set(from_readers) | set(from_reads):
        readers, reads_ = from_readers.get(name), from_reads.get(name)
        # the readers' scopes win, unless the operands' only add to them
        if readers is None or (reads_ is not None
                               and reads_[:len(readers)] == readers):
            scope[name] = reads_
        else:
            scope[name] = readers

    def caller_scope(computation: str, seen: frozenset) -> List[str]:
        paths = []
        for caller in callers.get(computation, []):
            path = scope.get(caller, [""])
            if path == [""] and home[caller] not in seen:
                path = caller_scope(home[caller], seen | {home[caller]})
            paths.append(path)
        return _shared(paths) if paths else []

    out = {}
    for name in key:
        path = scope.get(name, [""])
        if path == [""]:
            path = caller_scope(home[name], frozenset({home[name]}))
        out[key[name]] = "/".join(p for p in path if p)
    return out


def merge(tables) -> Dict[str, str]:
    """One table from several: an instruction two tables give different
    scope paths is ambiguous, and maps to "" (unscoped)."""
    out: Dict[str, str] = {}
    for table in tables:
        for key, path in table.items():
            out[key] = path if out.get(key, path) == path else ""
    return out


def scope_table() -> Dict[str, str]:
    """Short name -> scope path over the last :data:`KEEP` executables
    registered, read from their optimized HLO the first time each is
    asked for."""
    for entry in _compiled:
        if entry[1] is None:
            entry[1] = table_of(entry[0].as_text())
    return merge(table for _, table in _compiled)
