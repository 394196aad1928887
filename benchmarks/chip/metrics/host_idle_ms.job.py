"""Milliseconds a job the device sat idle while the host was inside one
of the engine's ``job/*`` spans (gaps named by the innermost program span
over their midpoint, ``scopes.py``)."""

import scopes


def read(run):
    jobs = run.window.jobs
    if run.trace is None or not jobs or not scopes.names_spans():
        return None
    idle = scopes.idle_by_span(run.trace)
    return 1e3 * sum(s for name, s in idle.items()
                     if name and name.startswith("job/")) / len(jobs)
