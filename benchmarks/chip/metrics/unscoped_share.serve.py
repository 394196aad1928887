"""Share of the device's busy time over the serving window, in percent,
that no owner inside the loop claims: operations no scope names, or named
only by the bare ``superstep`` loop (``scopes.unscoped``). What the scope
metrics cannot see."""

import scopes


def read(run):
    return scopes.unscoped_share(run) if run.window.answers else None
