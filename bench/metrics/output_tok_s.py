"""Every generated token of every request finished in the window, over
the window's seconds (host clock)."""


def read(obs):
    if obs.window_s <= 0:
        return None
    return sum(r.generated_tokens for r in obs.segments) / obs.window_s
