"""Share of the traced window in which no operation ran on the device,
averaged over the chips: 100 x (1 - busy / window)."""


def read(r):
    if r.window_s <= 0 or r.summary.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.summary.busy_s / r.window_s)
