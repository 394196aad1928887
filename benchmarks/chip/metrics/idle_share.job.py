"""Share of the traced window in which no operation ran on the device, in
percent: 1 - (union of device-op intervals) / window."""


def read(run):
    share = run.trace.idle_share() if run.window.jobs else None
    return None if share is None else 100.0 * share
