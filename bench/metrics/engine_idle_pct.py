"""Device idle inside an engine span other than ``engine.sync``, over
the traced window: the idle that the host loop's own work leaves
(``bench/spans.py``)."""


def read(obs):
    spans, t = getattr(obs, "spans", None), obs.trace
    if spans is None or t is None or t.window_s <= 0:
        return None
    return 100.0 * spans.engine_idle_s() / t.window_s
