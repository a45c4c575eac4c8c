"""Device milliseconds per step of the operations under ``tm/grad`` (the
model's forward and backward), per chip."""


def read(r):
    s = r.summary.layer_s.get("grad", 0.0)
    return 1e3 * s / r.steps if s > 0 else None
