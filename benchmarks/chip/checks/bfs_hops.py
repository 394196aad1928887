"""Hop counts of every answer of the window against the breadth-first
search of ``reference/bfs.py``. Two numbers, each with the limit 0:

- ``hop_mismatch``: over every answer, the vertices whose hop count
  differs from the reference's;
- ``answers_failed``: queries quarantined, overflowed or out of budget.
"""

import check
from reference import bfs

#: the programs whose answer is a hop count from the query's source
PROGRAMS = ("reach:basic",)


def judge(window, graph):
    csr = bfs.CSR(graph.n, graph.src, graph.dst)
    failed = mismatched = 0
    for answer in window.answers:
        if answer.status != "ok":
            failed += 1
            continue
        mismatched += check.hop_mismatch(answer.output,
                                         bfs.hops(csr, answer.source))
    return [check.Check("hop_mismatch", mismatched),
            check.Check("answers_failed", failed)]
