"""Seconds per job: from the window's start to the end of its last job,
over the jobs run. The job that straddles the close is finished and
counted, so all the work and all the time of the window are in it."""


def read(run):
    jobs = run.window.jobs
    return run.window.elapsed_s / len(jobs) if jobs else None
