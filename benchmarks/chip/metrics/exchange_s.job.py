"""Device seconds a job spends under the program's ``exchange`` scopes
(each collective and the unpack after it: the local transpose on one
chip), outside the pointer-jumping channels (``scopes.py``)."""

import scopes


def read(run):
    return scopes.per_job(run, scopes.outside_jump("exchange"))
