"""What decides ``correct``: served tokens against the float32 reference.

Once the window has closed, a sample of the finished requests, drawn
from the seed and always holding the longest, is run through the
cell's reference once, over each prompt followed by the tokens the
program served.  At every served position the reference's best logit is
compared with its logit for the served token.  Two numbers come of the
gaps: the widest over the sample, and their mean over every served
token.  A cell's limit file names the ones it compares.  Greedy decoding
in bfloat16 may take a near-tie the other way, so the gaps of a sound
run are small but not zero; a wrong cache, kernel or head serves tokens
that the reference puts far below its best.  The widest gap is one
extreme and swings from seed to seed; the mean counts how often and how
far the served tokens leave the reference's choice, and separates a
lower precision further (a lower precision flips more near-ties, and
each by more).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def sample(seed: int, done: Sequence[Tuple], k: int) -> List[Tuple]:
    """``k`` of the finished ``(request, stats)`` pairs: the longest (by
    prompt plus output), and ``k - 1`` others drawn from the seed."""
    if len(done) < k:
        raise ValueError(f"{len(done)} finished requests, {k} to compare")
    lengths = [len(r.prompt) + r.max_new_tokens for r, _ in done]
    first = int(np.argmax(lengths))
    rest = [i for i in range(len(done)) if i != first]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    picked = [first] + sorted(rng.choice(rest, size=k - 1, replace=False))
    return [done[i] for i in picked]


def padded_length(max_len: int, q_block: int) -> Tuple[int, int]:
    """Sequence length the reference runs at, and its query block: one
    fixed shape per cell, so its programs come from the compile cache."""
    if max_len <= q_block:
        return max_len, max_len
    return -(-max_len // q_block) * q_block, q_block


def compare(ref, seed: int, cfg: dict, sparsity, picked: Sequence[Tuple],
            max_len: int, max_new: int, q_block: int = 512):
    """The numbers compared (``widest_gap``, ``mean_gap``) over
    ``picked``, and each request's widest gap."""
    t, qb = padded_length(max_len, q_block)
    s = len(picked)
    tokens = np.zeros((s, t), np.int32)
    rows = np.zeros((s, max_new), np.int32)
    served = np.zeros((s, max_new), np.int32)
    valid = np.zeros((s, max_new), bool)
    for i, (req, st) in enumerate(picked):
        out = list(st.tokens)
        seq = list(req.prompt) + out[:-1]
        tokens[i, : len(seq)] = seq
        n = len(out)
        rows[i, :n] = len(req.prompt) - 1 + np.arange(n)
        served[i, :n] = out
        valid[i, :n] = True
    logits = ref.logits_at(seed, cfg, sparsity, tokens, rows, q_block=qb)
    gaps = ref.gaps(logits, served, valid)
    numbers = {"widest_gap": float(gaps.max()),
               "mean_gap": float(gaps.sum() / valid.sum())}
    return numbers, [float(g) for g in gaps.max(axis=-1)]
