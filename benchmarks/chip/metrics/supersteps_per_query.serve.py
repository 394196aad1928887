"""Supersteps a served query ran (``QueryRecord.steps``), averaged over
every query of the window."""


def read(run):
    a = run.window.answers
    return sum(x.steps for x in a) / len(a) if a else None
