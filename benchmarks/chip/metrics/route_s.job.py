"""Device seconds a job spends under the program's ``route`` scopes
(owners, keys, bucket ranks, slots, pack, dedup), outside the
pointer-jumping channels, which ``jump_s.job`` counts (``scopes.py``)."""

import scopes


def read(run):
    return scopes.per_job(run, scopes.outside_jump("route"))
