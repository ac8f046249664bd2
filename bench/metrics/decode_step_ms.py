"""Mean device time of one ``paged_decode_step`` program in the traced
segment."""


def read(obs):
    if obs.trace is None:
        return None
    times = obs.trace.programs.get("paged_decode_step")
    return 1e3 * sum(times) / len(times) if times else None
