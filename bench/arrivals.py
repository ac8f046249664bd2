"""Seeded traffic from a mix file: one general generator for every mix.

A mix file (``bench/traffic/<name>.json``) gives the engine's sizes, the
prompt and output lengths, the offered load, and the number of requests
per segment.  A length is drawn from one of three forms: ``values`` with
shares ``p``; an exponential of a given ``mean``; a log-normal of a given
``median`` and ``sigma``.  Each may be rounded to the nearest multiple of
a ``quantum`` and clipped to ``min`` and ``max``.

Every segment holds the same multiset of sizes and of gaps between
arrivals: values in exact proportion (largest remainders), continuous
lengths and gaps at the quantiles ``(i + 0.5) / n`` of their
distributions.  Segment ``j`` orders them by a fixed draw of its own,
the same on every seed; the seed draws the prompt tokens (and the
harness the weights).  The order is not the seed's because it changes
the work: which prompt meets which output length and when decides how
many engine iterations a segment takes (with seeds that also ordered
the requests, six seeds of one cell read 41.0 to 45.8 tokens/s, while
two runs of one seed differed by a median 0.5%, on a TPU v5e).  Arrival
times count scheduler iterations, the engine's own clock.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Drawn:
    """One request as the generator draws it."""

    rid: int
    prompt: Tuple[int, ...]
    max_new_tokens: int
    arrival: float


def lengths(spec: dict, n: int) -> List[int]:
    """``n`` lengths of the form ``spec`` states (module docstring)."""
    if "values" in spec:
        values = [int(v) for v in spec["values"]]
        shares = np.asarray(spec["p"], np.float64)
        exact = shares / shares.sum() * n
        counts = np.floor(exact).astype(int)
        order = np.argsort(-(exact - counts), kind="stable")
        counts[order[: n - counts.sum()]] += 1
        raw = [v for v, c in zip(values, counts) for _ in range(c)]
    else:
        qs = [(i + 0.5) / n for i in range(n)]
        if "mean" in spec:
            raw = [-spec["mean"] * math.log(1.0 - q) for q in qs]
        else:
            z = NormalDist()
            raw = [spec["median"] * math.exp(spec["sigma"] * z.inv_cdf(q))
                   for q in qs]
    quantum = int(spec.get("quantum", 1))
    lo, hi = int(spec.get("min", quantum)), int(spec.get("max", 1 << 30))
    return [min(hi, max(lo, quantum * round(x / quantum))) for x in raw]


def prompt_lengths(mix: dict, n: int) -> List[int]:
    return lengths(mix["prompt_tokens"], n)


def output_lengths(mix: dict, n: int) -> List[int]:
    return lengths(mix["output_tokens"], n)


def prefill_chunks(prompt: int, chunk: int) -> int:
    return -(-prompt // chunk)


def capacity_rate(mix: dict) -> float:
    """Requests per iteration at the mix's load share:
    ``load * slots / (mean prefill chunks + mean output tokens)``."""
    n = mix["segment_requests"]
    chunk = mix["engine"]["prefill_chunk"]
    chunks = np.mean([prefill_chunks(p, chunk)
                      for p in prompt_lengths(mix, n)])
    outs = np.mean(output_lengths(mix, n))
    return mix["load"] * mix["engine"]["slots"] / (chunks + outs)


def gaps(mix: dict, n: int) -> List[float]:
    """``n`` exponential gaps between arrivals at the quantiles."""
    rate = float(mix["rate_per_iteration"])
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


# the stream that orders every segment's sizes and gaps, whatever the seed
ORDER = 0x0DE7


def segment(mix: dict, seed: int, index: int, vocab: int) -> List[Drawn]:
    """Segment ``index``: its fixed order of sizes and arrivals, with
    prompt tokens that ``seed`` draws."""
    n = mix["segment_requests"]
    order = np.random.default_rng([ORDER, int(index)])
    prompts = order.permutation(np.asarray(prompt_lengths(mix, n)))
    outs = order.permutation(np.asarray(output_lengths(mix, n)))
    arrivals = np.cumsum(order.permutation(np.asarray(gaps(mix, n))))
    tokens = np.random.default_rng([int(seed), int(index)])
    return [Drawn(rid=i,
                  prompt=tuple(int(t) for t in
                               tokens.integers(1, vocab, int(p))),
                  max_new_tokens=int(o), arrival=float(a))
            for i, (p, o, a) in enumerate(zip(prompts, outs, arrivals))]


def chunk_lengths(mix: dict) -> List[int]:
    """Every prefill chunk length the mix's prompts produce (the engine
    compiles one prefill program for each)."""
    chunk = mix["engine"]["prefill_chunk"]
    out = set()
    for p in set(prompt_lengths(mix, mix["segment_requests"])):
        full, rest = divmod(p, chunk)
        if full:
            out.add(chunk)
        if rest:
            out.add(rest)
    return sorted(out)


def warmup_requests(mix: dict) -> List[Drawn]:
    """One request per prefill chunk length, each decoding one step."""
    return [Drawn(rid=i, prompt=tuple(range(1, c + 1)), max_new_tokens=2,
                  arrival=0.0)
            for i, c in enumerate(chunk_lengths(mix))]


def longest(requests: Sequence[Drawn]) -> int:
    return max(len(r.prompt) + r.max_new_tokens for r in requests)
