"""Lockstep reference loop (the pre-paging serving behavior).

Every slot advances one shared ``pos`` against dense contiguous caches:
a slot still prefilling burns decode steps feeding one prompt token at a
time, a finished request's slot idles until it is re-admitted at the
CURRENT shared position (so each recycled slot has less and less cache
runway), and the whole loop dies at ``pos == max_len - 1`` regardless of
how little each individual request consumed.

Kept as an executable baseline: the acceptance contract for the
continuous engine is *strictly higher completed-request throughput on
the same trace at equal batch width*, and ``tests/test_serving.py``
asserts exactly that against this loop.
"""

from __future__ import annotations

import time
from functools import partial
from typing import List, Sequence

import numpy as np

from .engine import RequestStats, ServingReport
from .scheduler import Request
from .spec import Prepared

__all__ = ["run_lockstep"]


def run_lockstep(prepared: Prepared, requests: Sequence[Request],
                 *, collect_tokens: bool = True) -> ServingReport:
    """Serve ``requests`` with the lockstep shared-``pos`` loop."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode_step, init_caches

    spec = prepared.spec
    cfg = prepared.cfg
    if cfg is None:
        raise ValueError("run_lockstep needs a full model: prepare(..., cfg=cfg)")
    params = prepared.params
    batch, max_len = spec.slots, spec.max_len

    step = partial(jax.jit, static_argnames=("cfg",))(decode_step)
    arrivals = sorted(requests, key=lambda r: (r.arrival, r.rid))
    n = len(arrivals)
    ai = 0
    slots: List = [None] * batch
    stats: List[RequestStats] = []
    # wall stamp at arrival (first eligibility), NOT at slot admission:
    # latency must include queue wait so the gated p50/p99 rows compare
    # the same enqueue->done definition the Engine reports
    arrive_wall = {}
    pos = steps = 0
    t0 = time.perf_counter()

    with prepared.activate():
        caches = init_caches(cfg, batch, max_len)
        while len(stats) < n and pos < max_len - 1:
            now_wall = time.perf_counter()
            while ai < n and arrivals[ai].arrival <= pos:
                arrive_wall[arrivals[ai].rid] = now_wall
                ai += 1
            arrived = arrivals[:ai]
            for s in range(batch):
                if slots[s] is None:
                    nxt_req = next((r for r in arrived
                                    if not any(a and a["req"].rid == r.rid
                                               for a in slots)
                                    and r.rid not in {st.rid for st in stats}),
                                   None)
                    if nxt_req is not None:
                        slots[s] = {"req": nxt_req, "i": 0, "out": [],
                                    "wall": arrive_wall[nxt_req.rid],
                                    "admit": now_wall, "first": None}
            if not any(slots) and ai < n:
                pos += 1     # idle step waiting for an arrival
                continue
            feed = []
            for s in range(batch):
                a = slots[s]
                if a is None:
                    feed.append(0)
                elif a["i"] < len(a["req"].prompt):
                    feed.append(a["req"].prompt[a["i"]])
                else:
                    feed.append(a["out"][-1])
            logits, caches = step(params, caches,
                                  jnp.asarray(feed, jnp.int32)[:, None],
                                  jnp.int32(pos), cfg=cfg)
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
            steps += 1
            for s in range(batch):
                a = slots[s]
                if a is None:
                    continue
                a["i"] += 1
                if a["i"] >= len(a["req"].prompt):
                    a["out"].append(int(nxt[s]))
                    if a["first"] is None:
                        a["first"] = time.perf_counter()
                if len(a["out"]) >= a["req"].max_new_tokens:
                    done_wall = time.perf_counter()
                    lat = done_wall - a["wall"]
                    stats.append(RequestStats(
                        rid=a["req"].rid, prompt_len=len(a["req"].prompt),
                        new_tokens=len(a["out"]),
                        tokens=tuple(a["out"]) if collect_tokens else (),
                        arrival=a["req"].arrival, done_iter=pos,
                        latency_s=lat, queue_s=a["admit"] - a["wall"],
                        ttft_s=a["first"] - a["wall"],
                        tokens_per_s=len(a["out"]) / lat if lat > 0 else 0.0))
                    slots[s] = None
            pos += 1

    return ServingReport(
        stats=sorted(stats, key=lambda s_: s_.rid),
        total=n, completed=len(stats),
        wall_s=time.perf_counter() - t0,
        model_calls=pos, prefill_chunks=0, decode_calls=pos,
        iterations=steps, evictions=0, max_blocks_in_use=0, num_blocks=0)
