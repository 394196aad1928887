"""Median wall time of one serving dispatch in milliseconds
(``ServeResult.dispatch_median_s``: enqueue to ``block_until_ready``)."""


def read(run):
    w = run.window
    return 1e3 * w.dispatch_median_s if w.dispatches else None
