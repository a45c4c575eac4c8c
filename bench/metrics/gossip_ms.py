"""Device milliseconds per step of the gossip mix: operations under
``tm/stage/gossip_mix``, ``tm/gossip/*`` and ``tm/launch_mix`` (the dense
contraction on one chip, the ppermute schedule across chips), per chip."""


def read(r):
    s = r.summary.layer_s.get("gossip", 0.0)
    return 1e3 * s / r.steps if s > 0 else None
