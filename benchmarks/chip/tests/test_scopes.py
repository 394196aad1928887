"""Device time and idle gaps charged to the program's own names
(``scopes.py``): on a synthetic trace with nested operations and host
spans, and on a recorded v5e trace of one ``sv:composed`` job at scale 10
with the scope table its process dumped beside it."""
import gzip
import json
import types

import jax
import pytest

import scopes
import trace
from conftest import HERE
from repro.pregel import tracing

DATA = HERE / "data"
TAG = "cc_s10_sv_scoped"

E = trace.Event
PLANE = "/device:TPU:0"


def hlo(name, opcode="fusion", shape="s32[8]"):
    return f"%{name} = {shape}{{0}} {opcode}(s32[8]{{0}} %p)"


def synthetic() -> trace.Trace:
    """A 100 ns window: a while loop (0-60) holding a route fusion (10-30)
    and an exchange copy (30-40), a combine fusion (70-80), and two ops
    no table names; host spans around them."""
    ops = [E(hlo("while.1", "while"), 0, 60),
           E(hlo("fusion.1"), 10, 30),
           E(hlo("copy.2", "copy"), 30, 40),
           E(hlo("fusion.3"), 70, 80),
           E(hlo("fusion.9"), 85, 90),       # in both tables, differently
           E(hlo("sort.4", "sort"), 90, 95)]  # in no table
    host = [E("bench/job", 0, 100),
            E("job/wait", 0, 62),
            E("job/readback", 60, 68),
            E("np.asarray(jax.Array)", 61, 67),
            E("job/extract", 80, 90),
            E("job/wait", 82, 84)]
    return trace.Trace((0, 100), {PLANE: ops}, host)


TABLES = [{"while.1 while s32[8]": "superstep",
           "fusion.1 fusion s32[8]": "superstep/sv/merge/route",
           "copy.2 copy s32[8]": "superstep/sv/jump/exchange",
           "fusion.3 fusion s32[8]": "superstep/sv/neighbor_min/combine",
           "fusion.9 fusion s32[8]": "superstep/sv/merge/route"},
          {"fusion.9 fusion s32[8]": "superstep/sv/jump/route"}]


def test_scoped_and_unscoped_time_add_up_to_busy_time():
    t = synthetic()
    charges = scopes.charge(t, tracing.merge(TABLES))
    assert sum(charges.values()) == pytest.approx(t.busy_s())
    assert charges["superstep"] == pytest.approx(30e-9)      # 60 less 30
    assert charges["superstep/sv/merge/route"] == pytest.approx(20e-9)


def test_unknown_and_ambiguous_ops_are_unscoped():
    charges = scopes.charge(synthetic(), tracing.merge(TABLES))
    # fusion.9 (claimed by two tables) and sort.4 (by none)
    assert charges[""] == pytest.approx(10e-9)
    alone = scopes.charge(synthetic(), TABLES[0])
    assert alone[""] == pytest.approx(5e-9)


def test_time_under_the_bare_loop_is_unscoped(monkeypatch):
    monkeypatch.setattr(scopes, "table", lambda: tracing.merge(TABLES))
    run = types.SimpleNamespace(trace=synthetic(), window=None)
    # while.1's own 30 ns, fusion.9 (ambiguous) and sort.4 (unknown)
    assert scopes.unscoped_share(run) == pytest.approx(100 * 40 / 80)
    assert scopes.unscoped("") and scopes.unscoped("superstep")
    for owned in ("superstep/vote", "superstep/compute",
                  "superstep/compute/sv/jump"):
        assert not scopes.unscoped(owned), owned


def test_phases_and_channels():
    charges = scopes.charge(synthetic(), tracing.merge(TABLES))
    pick = scopes.seconds
    assert pick(charges, scopes.outside_jump("route")) == \
        pytest.approx(20e-9)
    assert pick(charges, scopes.outside_jump("exchange")) == 0
    assert pick(charges, lambda p: any(scopes.under(p, ch)
                                       for ch in scopes.JUMP)) == \
        pytest.approx(10e-9)
    assert scopes.phase("superstep/sv/merge/route/exchange") == "exchange"
    assert scopes.phase("superstep/vote") is None
    assert not scopes.under("superstep/sv/jumper", "sv/jump")


def test_gaps_are_named_by_the_innermost_program_span():
    t = synthetic()
    assert scopes.gaps(t) == [(60, 70), (80, 85), (95, 100)]
    idle = scopes.idle_by_span(t)
    # 60-70: midpoint 65 is inside job/readback, not np.asarray
    assert idle["job/readback"] == pytest.approx(10e-9)
    # 80-85: midpoint 82 is inside job/wait, within job/extract
    assert idle["job/wait"] == pytest.approx(5e-9)
    # 95-100: under bench/job only
    assert idle[None] == pytest.approx(5e-9)
    assert "np.asarray(jax.Array)" not in idle


def test_readers_return_nothing_without_program_names(monkeypatch):
    monkeypatch.setattr(scopes, "_tracing", lambda: None)
    run = types.SimpleNamespace(
        trace=synthetic(), window=types.SimpleNamespace(
            jobs=[object()], answers=[], dispatches=0))
    assert scopes.run_charges(run) is None
    assert scopes.per_job(run, lambda p: True) is None
    assert scopes.unscoped_share(run) is None
    assert not scopes.names_spans()


def load_recorded():
    raw = gzip.decompress((DATA / f"{TAG}.xplane.pb.gz").read_bytes())
    t = trace.reduce(jax.profiler.ProfileData.from_serialized_xspace(raw),
                     span=f"bench/{TAG}")
    table = json.loads(gzip.decompress(
        (DATA / f"{TAG}.scopes.json.gz").read_bytes()))
    return t, table


def test_recorded_sv_job_reads_all_three_phases():
    t, table = load_recorded()
    charges = scopes.charge(t, table)
    assert sum(charges.values()) == pytest.approx(t.busy_s(), rel=1e-6)
    for name in scopes.PHASES:
        assert scopes.seconds(charges, scopes.outside_jump(name)) > 0, name
    assert scopes.seconds(charges, lambda p: any(
        scopes.under(p, ch) for ch in scopes.JUMP)) > 0
    assert scopes.seconds(charges, lambda p: not scopes.unscoped(p)) > \
        0.9 * t.busy_s()
    # the job's host spans lie on the window's thread
    assert {"job/dispatch", "job/wait", "job/extract"} <= \
        {ev.name for ev in t.host}
