"""Megabytes (1e6 bytes) sent over every channel per job, as the channels
count them on the device (``RunResult.total_bytes``)."""


def read(run):
    jobs = run.window.jobs
    return sum(j.total_bytes for j in jobs) / len(jobs) / 1e6 if jobs \
        else None
