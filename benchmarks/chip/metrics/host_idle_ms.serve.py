"""Milliseconds a serving dispatch the device sat idle while the host was
inside one of the serve loop's spans other than ``serve/wait``: admission,
dispatch, readback, accounting and harvest (``scopes.py``)."""

import scopes


def read(run):
    w = run.window
    if run.trace is None or not w.dispatches or not scopes.names_spans():
        return None
    idle = scopes.idle_by_span(run.trace)
    return 1e3 * sum(s for name, s in idle.items()
                     if name and name.startswith("serve/")
                     and name != "serve/wait") / w.dispatches
