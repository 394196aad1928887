"""Answers per second: the queries issued in the window and answered, over
the time from the window's start to the last of their harvests. Issuing
stops at the close and the queries in flight are served to the end, as a
job that straddles the close is, so all the work and all the time of the
window are in it."""


def read(run):
    w = run.window
    done = sum(a.status == "ok" for a in w.answers)
    return done / w.elapsed_s if w.answers else None
