"""Device seconds a job spends under the program's ``combine`` scopes:
``kops.segment_combine`` and ``gather_segment_combine``, the Pallas
kernel or the reference, outside the pointer-jumping channels
(``scopes.py``). The gathers that feed them are their channel's."""

import scopes


def read(run):
    return scopes.per_job(run, scopes.outside_jump("combine"))
