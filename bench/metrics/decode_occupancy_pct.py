"""Share of decode-step slots that carried a live token in the traced
segment: tokens the decode steps emitted over decode calls x slots
(the engine's own counts; each request's first token comes from its
prefill)."""


def read(obs):
    r = obs.traced_report
    if r is None or not r.decode_calls:
        return None
    return 100.0 * (r.generated_tokens - r.completed) / (
        r.decode_calls * obs.slots)
