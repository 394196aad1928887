"""Device seconds a job spends under composed S-V's pointer-jumping
channels, ``sv/jump`` and ``sv/pointer/*``, in every phase
(``scopes.py``)."""

import scopes


def read(run):
    return scopes.per_job(run, lambda path: any(
        scopes.under(path, ch) for ch in scopes.JUMP))
