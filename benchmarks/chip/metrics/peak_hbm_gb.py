"""Peak device memory in GB (1e9 bytes) on the fullest chip, read after the
window: the graph a chip can hold is what analytics users are bound by."""


def read(run):
    return run.device["memory_peak_bytes"] / 1e9
