"""Model FLOPs of the traced segment's finished requests over (its
seconds x the cell's chips x one chip's bf16 peak).  The FLOPs are the
configuration's mathematics: nonzero weights, attention over live
positions, and the output head where a token is sampled."""

from bench import counting


def read(obs):
    r, peak = obs.traced_report, obs.peak()
    if r is None or peak is None or obs.trace is None:
        return None
    ref, cfg = obs.cell.reference(), obs.cell.config
    z = ref.dims(cfg)
    flops = counting.window_flops(
        [(s.prompt_len, s.new_tokens) for s in r.stats],
        ref.linear_shapes(cfg), z["layers"], z["heads"], z["head_dim"],
        z["d"], z["vocab"], obs.cell.sparsity)
    return 100.0 * flops / (obs.trace.window_s * obs.cell.chips
                            * peak["bf16_flops_per_s"])
