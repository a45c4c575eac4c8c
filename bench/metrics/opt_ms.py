"""Device milliseconds per step of the optimizer chain: operations under
``tm/opt_step`` that are not gossip, per chip."""


def read(r):
    s = r.summary.layer_s.get("opt", 0.0)
    return 1e3 * s / r.steps if s > 0 else None
