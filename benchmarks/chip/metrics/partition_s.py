"""Host seconds of ``pgraph.partition_graph``: the system's ingest of the
edge list (relabelling and every channel plan), on the host clock."""


def read(run):
    return run.partition_s
