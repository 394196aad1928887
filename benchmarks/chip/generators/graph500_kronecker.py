"""Graph500 Kronecker graph (specification v3, section 3; ``graph500.py``),
undirected as kernel 1 builds it. Reads every key of the configuration's
``graph`` object and refuses any other."""

import graph500

KEYS = {"generator", "scale", "edge_factor", "seed", "initiator",
        "undirected"}


def make(spec: dict):
    if set(spec) != KEYS:
        raise ValueError(f"graph500_kronecker reads exactly {sorted(KEYS)}, "
                         f"got {sorted(spec)}")
    if spec["undirected"] is not True:
        raise ValueError("graph500_kronecker makes undirected graphs only")
    return graph500.generate(spec["scale"], spec["edge_factor"], spec["seed"],
                             tuple(spec["initiator"]))
