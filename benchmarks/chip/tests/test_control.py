"""The control must come out not correct.

The configurations state exact answers and no precision, so the control
breaks the guarantee that the answer is the fixpoint: the plain reference,
put in the program's place, stopped one superstep short (components with
their last label-changing round left out; a search with its farthest level
left out). Its readings are the upper ends of the limits of ``checks/``.

As a test it runs at scale 12. At the cells' own size (their configurations'
graphs, and the queries a window of ``serve-bfs`` answers), on the chip's
host:

    python3 benchmarks/chip/tests/test_control.py

The cells' inputs are the same on every seed, so their readings are too.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path.insert(0, str(CHIP))

import numpy as np  # noqa: E402

import check  # noqa: E402
import graph500  # noqa: E402
import graphs  # noqa: E402
import manifest  # noqa: E402
import run  # noqa: E402
from reference import bfs, components  # noqa: E402

def components_reading(g) -> int:
    ref = components.components(g.n, g.src, g.dst)
    short = components.components_one_round_short(g.n, g.src, g.dst)
    return check.label_mismatch(short, ref)


def least_bfs_reading(g, sources) -> int:
    """The smallest mismatch a single short answer gives."""
    csr = bfs.CSR(g.n, g.src, g.dst)
    least = None
    for s in sources:
        short = bfs.hops_one_level_short(csr, int(s))
        m = check.hop_mismatch(np.where(short < 0, check.UNREACHED, short),
                               bfs.hops(csr, int(s)))
        least = m if least is None else min(least, m)
    return least


def test_components_control_fails():
    for seed in (1, 2, 3):
        assert components_reading(graph500.generate(12, 16, seed)) > 0


def test_bfs_control_fails_on_every_answer():
    g = graph500.generate(12, 16, 1)
    for seed in (1, 2, 3):
        assert least_bfs_reading(g, graphs.search_keys(g, seed, 40)) > 0


def _graph(m, config: dict):
    return graphs.cached(config["graph"], run.GRAPHS, m.generator(config).make)


def main() -> int:
    m = manifest.Manifest.load()
    config = m.config("g500-s20-cc")
    t = time.perf_counter()
    cc = components_reading(_graph(m, config))
    print(json.dumps({"config": config["name"], "label_mismatch": cc,
                      "seconds": round(time.perf_counter() - t, 2)}),
          flush=True)
    # the queries serve-bfs answers: the same on every seed (traffic/closed_loop.py)
    config = m.config("g500-s17-bfs-serve")
    traffic = m.traffic("bfs-closed-8")
    lanes = config["serve"]["lanes"]
    g = _graph(m, config)
    t = time.perf_counter()
    keys = graphs.search_keys(g, config["graph"]["seed"],
                                (traffic["rounds"] + 1) * lanes)[lanes:]
    least = least_bfs_reading(g, keys)
    print(json.dumps({"config": config["name"], "answers": len(keys),
                      "hop_mismatch_least_answer": least,
                      "seconds": round(time.perf_counter() - t, 2)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
