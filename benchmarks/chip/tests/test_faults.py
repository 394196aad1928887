"""A run whose timed path is broken underneath must come out not correct.

Each fault is planted under the harness, in the program it drives, and the
rest of the run goes as on the chip (on the CPU, at a small size): the
window, the comparison with the references, the result line.
"""
import dataclasses

import jax
import numpy as np
import pytest

import drive
import run
from conftest import result_line

CELLS = ["cc-sv", "cc-wcc-prop", "serve-bfs"]


def _wrap_program(monkeypatch, change):
    original = drive.get_program
    made = {}

    def get_program(key):
        if key not in made:
            made[key] = change(original(key))
        return made[key]

    monkeypatch.setattr(drive, "get_program", get_program)


def state_unchanged(monkeypatch):
    """Every superstep returns the state it was given, and so votes to
    halt."""
    def change(prog):
        def step(ctx, gs, state, i):
            out = prog.step(ctx, gs, state, i)
            return (state, jax.numpy.asarray(True)) + tuple(out[2:])
        return dataclasses.replace(prog, step=step)
    _wrap_program(monkeypatch, change)


def half_the_edges(monkeypatch):
    """Ingest keeps every other edge: half of the work left out."""
    from repro.graph import pgraph

    original = pgraph.partition_graph

    def partition_graph(g, *a, **kw):
        half = dataclasses.replace(g, edges=g.edges[::2])
        return original(half, *a, **kw)

    monkeypatch.setattr(pgraph, "partition_graph", partition_graph)


def no_exchange(monkeypatch):
    """Every all_to_all between workers returns what was sent."""
    monkeypatch.setattr(jax.lax, "all_to_all",
                        lambda x, *a, **kw: x)


def answer_altered(monkeypatch):
    """One vertex's answer is changed where the program produces it."""
    def change(prog):
        def extract(pg, state):
            out = np.array(prog.extract(pg, state))
            v = int(np.argmax(np.asarray(pg.to_global(pg.deg_out))))
            out[v] = out.max() + 1 if out[v] == out.min() else out[v] + 1
            return out
        return dataclasses.replace(prog, extract=extract)
    _wrap_program(monkeypatch, change)


FAULTS = [state_unchanged, half_the_edges, no_exchange, answer_altered]


def test_sound_runs_are_correct(small_manifest, cpu_devices, capsys):
    for cell in CELLS:
        run.main(["--workload", cell, "--seed", "5", "--seconds", "0.3"],
                 devices=cpu_devices, manifest_path=small_manifest)
        assert result_line(capsys)["correct"] is True, cell


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_caught(fault, cell, small_manifest, cpu_devices, capsys,
                         monkeypatch):
    fault(monkeypatch)
    run.main(["--workload", cell, "--seed", "5", "--seconds", "0.3"],
             devices=cpu_devices, manifest_path=small_manifest)
    line = result_line(capsys)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
