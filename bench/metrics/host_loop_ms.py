"""Mean over the traced segment's ``engine.iter`` spans of their
duration less the ``engine.sync`` spans inside them: the host loop's own
time per iteration (``bench/spans.py``)."""


def read(obs):
    spans = getattr(obs, "spans", None)
    return None if spans is None else spans.host_loop_ms()
