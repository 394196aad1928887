"""Share of its roofline the segment_combine kernel reached over the traced window,
in percent: the least time its calls could take on this chip (bytes over
peak HBM bandwidth; the kernel is memory bound) over the device time they
took. Calls, bytes and operations come from ``kernel_cost.py``."""

import kernel_cost


def read(run):
    if run.trace is None:
        return None
    return kernel_cost.roofline_share(run.trace, "segment_combine", run.device["kind"])
