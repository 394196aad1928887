"""Connected-component labels of every job of the window against
``reference/components.py``. Two numbers, each with the limit 0:

- ``label_mismatch``: for the worst job, the vertices whose component (as a
  partition of the vertices) differs from the reference's;
- ``jobs_not_halted``: jobs that stopped on their step budget.
"""

import check
from reference import components

#: the programs whose output is a component label for every vertex
PROGRAMS = ("wcc:basic", "wcc:prop", "wcc:switch", "sv:basic", "sv:reqresp",
            "sv:scatter", "sv:both", "sv:monolithic", "sv:composed")


def judge(window, graph):
    ref = components.components(graph.n, graph.src, graph.dst)
    worst = max(check.label_mismatch(job.output, ref) for job in window.jobs)
    return [check.Check("label_mismatch", worst),
            check.Check("jobs_not_halted",
                        sum(not job.halted for job in window.jobs))]
