"""Device milliseconds a serving dispatch spends under the program's
``route`` scopes: the union-frontier route (``scopes.py``)."""

import scopes


def read(run):
    w = run.window
    charges = scopes.run_charges(run) if w.dispatches else None
    if charges is None:
        return None
    return 1e3 * scopes.seconds(
        charges, lambda path: scopes.phase(path) == "route") / w.dispatches
