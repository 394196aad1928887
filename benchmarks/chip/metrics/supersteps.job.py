"""Supersteps per job (``RunResult.steps``, the loop driver's counter),
averaged over the window's jobs."""


def read(run):
    jobs = run.window.jobs
    return sum(j.steps for j in jobs) / len(jobs) if jobs else None
