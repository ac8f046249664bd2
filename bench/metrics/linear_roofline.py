"""The linear kernels' share of their roofline in the traced segment:
the sum over calls of the least time the chip needs (the larger of
FLOPs over peak and bytes over bandwidth, counted from each call's
stored operands and rows) over the sum of their device times."""


def read(obs):
    t = obs.trace
    if t is None or not t.linear_calls or t.linear_least_s is None:
        return None
    return 100.0 * t.linear_least_s / t.linear_s
