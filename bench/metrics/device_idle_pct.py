"""Share of the traced segment in which no operation ran on the device:
1 - union of busy intervals / traced window."""


def read(obs):
    t = obs.trace
    if t is None or t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
