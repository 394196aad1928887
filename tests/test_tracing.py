"""The engine's names for its own work (``repro.pregel.tracing``).

Device scopes: every channel of a compiled loop owns instructions under
its stat key, and the three phases each own some, read back from the
optimized HLO's metadata. Host spans: a job and a served session emit
theirs into a profiler trace. All on the CPU at scale 10.
"""
import functools
import glob

import jax
import pytest

from repro.algorithms import REGISTRY
from repro.graph import pgraph
from repro.pregel import tracing
from repro.pregel.engine import Engine

SCALE, SEED, W, LANES = 10, 0, 8, 8


@functools.lru_cache(maxsize=None)
def problem(key):
    spec = REGISTRY[key]
    graph = spec.make_graph(SCALE, SEED)
    pg = pgraph.partition_graph(graph, W, "random", build=spec.build)
    return spec, graph, pg, spec.make(graph, SEED)


@functools.lru_cache(maxsize=None)
def compiled(key):
    """(bytes_by_channel keys, the executable's scope table) of one job of
    ``sv:composed`` (fused) or one served session of ``reach:basic``."""
    spec, graph, pg, prog = problem(key)
    if key == "reach:basic":
        eng = Engine(mode="chunked", chunk_size=4)
        res = eng.serve(prog, pg, spec.queries(graph, SEED, LANES),
                        num_lanes=LANES)
    else:
        eng = Engine(mode="fused")
        res = eng.run(prog, pg)
    (exe,) = eng._cache.values()
    return set(res.bytes_by_channel), tracing.table_of(exe._fn.as_text())


@pytest.mark.parametrize("key", ["sv:composed", "reach:basic"])
def test_every_channel_owns_a_scope(key):
    channels, table = compiled(key)
    assert channels
    paths = {f"/{p}/" for p in table.values()}
    for name in channels:
        assert any(f"/{name}/" in p for p in paths), name
    # the registry reads the same table back
    merged = tracing.scope_table()
    assert {k: v for k, v in merged.items() if k in table and v} \
        .items() <= table.items()


@pytest.mark.parametrize("key", ["sv:composed", "reach:basic"])
def test_every_phase_owns_instructions(key):
    channels, table = compiled(key)
    for phase in tracing.PHASES:
        assert any(phase in p.split("/") for p in table.values()), phase
    assert f"{tracing.SUPERSTEP}/{tracing.VOTE}" in table.values()
    # inside the loop every channel runs in the program's step function
    # (a reducer's own computation carries names relative to its reduce)
    loop = f"{tracing.SUPERSTEP}/"
    for name in channels:
        assert all(p.startswith(loop + tracing.COMPUTE + "/")
                   for p in table.values()
                   if p.startswith(loop) and f"/{name}/" in f"/{p}/"), name


@pytest.mark.parametrize("op_name,path", [
    ("jit(loop)/while/body/vmap(sv/jump)/add", "sv/jump"),
    ("jit(loop)/while/body/vmap(superstep)/sv/merge/route/mul",
     "superstep/sv/merge/route"),
    ("jit(chunk)/while/body/closed_call/cond/branch_1_fun/"
     "vmap(vmap(superstep))/combined_message/route/exchange/transpose",
     "superstep/combined_message/route/exchange"),
    ("jit(loop)/while/body/vmap(superstep)/propagation/while/body_pred/lt",
     "superstep/propagation"),
    # an instruction merged from two keeps the scopes both share
    ("jit(loop)/while/body/vmap(superstep)/sv/merge/combine/broadcast_in_dim"
     ";jit(loop)/while/body/vmap(superstep)/sv/merge/exchange/reshape",
     "superstep/sv/merge"),
    ("jit(chunk)/superstep/while/body/closed_call/cond/branch_0_fun/"
     "vmap(vmap(compute))/combined_message/route/gather",
     "superstep/compute/combined_message/route"),
    ("jit(loop)/while", ""),
    ("x", ""),
])
def test_scope_path_strips_jax_wrappers(op_name, path):
    assert tracing.scope_path(op_name) == path


def test_instructions_without_metadata_take_their_neighbours_scope():
    hlo = """
%body (q: s32[8]) -> (s32[8], s32[8]) {
  %q = s32[8]{0} parameter(0)
  %copy.5 = s32[8]{0} copy(%q)
  %add.6 = s32[8]{0} add(%q, %q), metadata={op_name="jit(f)/while/body/\
vmap(superstep)/vote/add"}
  ROOT %tuple.7 = (s32[8]{0}, s32[8]{0}) tuple(%copy.5, %add.6)
}

ENTRY %main (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0), metadata={op_name="p"}
  %fusion.6 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fused
  %reshape.9 = s32[8]{0} reshape(%fusion.6), metadata={op_name=\
"jit(f)/superstep/c/route/scatter-add"}
  %while.3 = (s32[8]{0}, s32[8]{0}) while(%reshape.9), condition=%cond, \
body=%body, metadata={op_name="jit(f)/superstep/while"}
  ROOT %copy.1 = s32[8]{0} copy(%p)
}
"""
    table = tracing.table_of(hlo)
    # the fusion the compiler left bare did its reader's work
    assert table["fusion.6 fusion s32[8]"] == "superstep/c/route"
    assert table["reshape.9 reshape s32[8]"] == "superstep/c/route"
    # a bare copy in a loop body is the loop's, whatever the tuple holds
    assert table["copy.5 copy s32[8]"] == "superstep"
    assert table["copy.1 copy s32[8]"] == ""


def test_a_rewritten_scatter_takes_its_readers_scope():
    """The compiler's sort and sorted scatter (both bare) did the work of
    the code that reads the scatter's result, through the sort's tuple
    elements; where the readers share less than the operands, the
    operands' scopes, which only add to them, win."""
    hlo = """
ENTRY %main (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  %ids = s32[8]{0} negate(%p), metadata={op_name="jit(f)/superstep/\
compute/c/route/neg"}
  %vals = s32[8]{0} abs(%p), metadata={op_name="jit(f)/superstep/\
compute/abs"}
  %sort.1 = (s32[8]{0}, s32[8]{0}) sort(%ids, %vals), dimensions={0}
  %gte.1 = s32[8]{0} get-tuple-element(%sort.1), index=0
  %gte.2 = s32[8]{0} get-tuple-element(%sort.1), index=1
  %fusion.2 = s32[8]{0} fusion(%gte.1, %gte.2), kind=kCustom, calls=%f
  %max.3 = s32[8]{0} maximum(%fusion.2, %p), metadata={op_name=\
"jit(f)/superstep/compute/c/route/max"}
  %fusion.4 = s32[8]{0} fusion(%ids, %ids), kind=kCustom, calls=%f
  %add.5 = s32[8]{0} add(%fusion.4, %p), metadata={op_name=\
"jit(f)/superstep/compute/add"}
  %sub.6 = s32[8]{0} subtract(%fusion.4, %p), metadata={op_name=\
"jit(f)/superstep/vote/sub"}
  ROOT %t = (s32[8]{0}, s32[8]{0}, s32[8]{0}) tuple(%max.3, %add.5, %sub.6)
}
"""
    table = tracing.table_of(hlo)
    assert table["sort.1 sort s32[8]"] == "superstep/compute/c/route"
    assert table["fusion.2 fusion s32[8]"] == "superstep/compute/c/route"
    # read by compute and vote (sharing only the loop), made from route
    assert table["fusion.4 fusion s32[8]"] == "superstep/compute/c/route"


def test_conflicting_tables_leave_the_instruction_unscoped():
    a = {"fusion.1 fusion s32[8]": "superstep/a/route", "x x": "v"}
    b = {"fusion.1 fusion s32[8]": "superstep/b/route", "x x": "v"}
    assert tracing.merge([a, b]) == {"fusion.1 fusion s32[8]": "",
                                     "x x": "v"}


def test_short_name_is_the_trace_form():
    hlo = ("%fusion.286 = s32[33554432]{0:T(1024)} fusion(s32[8,131072]"
           "{1,0:T(8,128)S(1)} %custom-call.66), kind=kCustom")
    assert tracing.short_name(hlo) == "fusion.286 fusion s32[33554432]"


def test_names_are_checked():
    with pytest.raises(ValueError):
        tracing.scope("routing")
    with pytest.raises(ValueError):
        tracing.span("job/everything")


def _host_events(logdir):
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    return {ev.name for plane in profile.planes
            if plane.name.startswith("/host")
            for line in plane.lines for ev in line.events}


def test_jobs_and_sessions_emit_their_spans(tmp_path):
    spec, graph, pg, prog = problem("reach:basic")
    queries = spec.queries(graph, SEED, LANES)
    eng = Engine(mode="chunked", chunk_size=4)
    eng.serve(prog, pg, queries[:2], num_lanes=2)          # compiles
    job = Engine(mode="fused")
    _, _, cpg, cprog = problem("sv:composed")
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = eng.serve(prog, pg, queries, num_lanes=2)
        out = job.run(cprog, cpg)                          # compiles
    finally:
        jax.profiler.stop_trace()
    assert res.num_failed == 0 and out.halted
    names = _host_events(tmp_path)
    assert set(tracing.SERVE_SPANS) <= names
    assert set(tracing.JOB_SPANS) | {tracing.COMPILE} <= names
