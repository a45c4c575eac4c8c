"""Device milliseconds per step in which a collective ran and nothing else
did (``Summary.exposed_collective_s``, the largest over the chips): the part
of the cross-chip gossip, and of the reductions beside it, that the step
does not hide under compute."""


def read(r):
    s = r.summary.exposed_collective_s
    return 1e3 * s / r.steps if s > 0 else None
