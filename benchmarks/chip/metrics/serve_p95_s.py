"""95th percentile of answer time (harvest minus due) over every query
issued in the window, those finished after its close included."""

import numpy as np


def read(run):
    lat = [a.done_s - a.due_s for a in run.window.answers]
    return float(np.percentile(lat, 95)) if lat else None
