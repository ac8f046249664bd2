"""Device time of the linear kernels over device busy time, in the
traced segment."""


def read(obs):
    t = obs.trace
    if t is None or t.busy_s <= 0 or not t.linear_calls:
        return None
    return 100.0 * t.linear_s / t.busy_s
