"""Continuous-batching engine over the paged KV cache.

Each iteration of :meth:`Engine.run`:

1. **arrivals** — requests whose Poisson timestamp has come enter the
   waiting queue (idle iterations fast-forward to the next arrival);
2. **admission** — free slots fill from the queue under the block
   budget (newly admitted slots get their SSM state zeroed);
3. **one prefill chunk** — the oldest prefilling request advances by up
   to ``prefill_chunk`` prompt tokens in a single model call (chunks are
   exact-sized, so MoE capacity never sees padding tokens);
4. **one batched decode step** — every decode-state slot advances its
   OWN position via the block-table decode path; idle / prefilling slots
   ride along masked.

Finished requests retire independently (ragged lengths), their blocks
return to the pool, and the slot admits the next arrival on the next
iteration — no stream ever waits for the whole batch to drain, which is
exactly what the lockstep loop (``repro.serving.baseline``) cannot do.

Sampled tokens stay on the device: each slot's last token is one
``(slots, 1)`` array that the next decode step reads as its feed.  The
schedule never reads a token's value (retirement counts tokens), so the
host reads each step's tokens one step late, after it has dispatched
the next: the chip always has a step queued while the host works.  A
request's stats are finished when its last token reaches the host.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, List, Optional, Sequence

import numpy as np

from .scheduler import PagedScheduler, Request, SlotState
from .spec import Prepared

__all__ = ["Engine", "RequestStats", "ServingReport", "percentile"]


def percentile(values: Sequence[float], p: float) -> float:
    """Linear-interpolation percentile (numpy-free contract for docs)."""
    if not values:
        return 0.0
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    rank = (p / 100.0) * (len(xs) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


@dataclasses.dataclass
class RequestStats:
    rid: int
    prompt_len: int
    new_tokens: int
    tokens: tuple           # the generated token ids
    arrival: float          # scheduler-iteration timestamp
    done_iter: int
    latency_s: float        # wall: enqueue -> last token
    queue_s: float          # wall: enqueue -> first admission
    ttft_s: float           # wall: enqueue -> first token
    tokens_per_s: float     # generated tokens / latency


@dataclasses.dataclass
class ServingReport:
    """What a serving run did — the benchmark CSV rows come from here."""

    stats: List[RequestStats]
    total: int
    completed: int
    wall_s: float
    model_calls: int        # prefill chunks + decode steps (lockstep: steps)
    prefill_chunks: int
    decode_calls: int
    iterations: int         # work iterations (one engine.iter span each)
    evictions: int
    max_blocks_in_use: int
    num_blocks: int
    # decode steps dispatched while the previous step's tokens were unread
    overlapped_steps: int = 0

    @property
    def p50_latency_s(self) -> float:
        return percentile([s.latency_s for s in self.stats], 50.0)

    @property
    def p99_latency_s(self) -> float:
        return percentile([s.latency_s for s in self.stats], 99.0)

    @property
    def generated_tokens(self) -> int:
        return sum(s.new_tokens for s in self.stats)

    @property
    def tokens_per_s(self) -> float:
        return self.generated_tokens / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def completed_per_call(self) -> float:
        """Completed-request throughput per model invocation — the
        wall-clock-free comparison axis between engines (a model call
        costs one forward regardless of which loop issued it)."""
        return self.completed / self.model_calls if self.model_calls else 0.0

    def describe(self) -> str:
        queue = percentile([s.queue_s for s in self.stats], 95.0)
        ttft = percentile([s.ttft_s for s in self.stats], 95.0)
        return (f"{self.completed}/{self.total} requests in "
                f"{self.wall_s:.2f}s over {self.model_calls} model calls "
                f"({self.tokens_per_s:.1f} tok/s, "
                f"p50 {self.p50_latency_s * 1e3:.0f}ms / "
                f"p99 {self.p99_latency_s * 1e3:.0f}ms, "
                f"p95 queue {queue * 1e3:.0f}ms / "
                f"p95 first token {ttft * 1e3:.0f}ms, "
                f"{self.evictions} eviction(s), "
                f"{self.overlapped_steps}/{self.decode_calls} decode steps "
                f"overlapped, "
                f"peak {self.max_blocks_in_use}/{self.num_blocks} blocks)")


@functools.lru_cache(maxsize=None)
def _token_programs(placement):
    """The two device programs that keep sampled tokens on the device,
    jitted once per ``placement`` of the token array, which their
    results keep: the decode step and they themselves then see one
    signature every step, and never retrace."""
    import jax
    import jax.numpy as jnp

    def first(tokens, logits, slot, n_valid):
        """Slot ``slot``'s first token: the argmax of its prefill
        chunk's last valid logit."""
        last = jax.lax.dynamic_index_in_dim(logits[0], n_valid - 1,
                                            keepdims=False)
        return tokens.at[slot, 0].set(jnp.argmax(last).astype(tokens.dtype))

    def following(logits, tokens, active):
        """Each decoding slot's next token; other slots keep theirs."""
        nxt = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
        return jnp.where(active[:, None], nxt, tokens)

    return (jax.jit(first, out_shardings=placement),
            jax.jit(following, out_shardings=placement))


class Engine:
    """Continuous-batching serving engine.

    ``Engine(prepare(params, spec, cfg=cfg)).run(requests)`` is the whole
    public serving API; ``launch/serve.py`` is a thin argparse adapter
    over it.  The jitted steps live at module level in
    ``repro.models.paged`` with the hashable config static, so engines
    over the same config share compiled traces.
    """

    def __init__(self, prepared: Prepared):
        if prepared.cfg is None:
            raise ValueError("Engine needs a full model: prepare(..., cfg=cfg)")
        self.prepared = prepared
        self.spec = prepared.spec
        self.cfg = prepared.cfg
        self.num_blocks = (self.spec.kv_blocks
                           if self.spec.kv_blocks is not None
                           else self.spec.default_kv_blocks())
        self._placed_caches = None    # the jitted, placed birth

    def _init_caches(self):
        from repro.models.paged import init_paged_caches
        # +1: physical block 0 is the scratch target for masked writes
        return init_paged_caches(self.cfg, self.num_blocks + 1,
                                 self.spec.block_len, self.spec.slots,
                                 kv_qdtype=self.spec.kv_qdtype)

    def _fresh_caches(self):
        """A new KV pool, born committed where the paged steps return it,
        so every segment runs the same programs: on a mesh placed as the
        steps take it (``repro.models.paged.paged_cache_shardings``), so
        no chip holds another's heads; off a mesh where a feed goes."""
        if self._placed_caches is None:
            import jax
            env = self.prepared.axis_env
            if env is None:
                placed = self._feed_placement()
            else:
                from repro.models.paged import paged_cache_shardings
                placed = paged_cache_shardings(
                    env, jax.eval_shape(self._init_caches))
            self._placed_caches = jax.jit(self._init_caches,
                                          out_shardings=placed)
        return self._placed_caches()

    def _feed_placement(self):
        """Where :meth:`_feed` puts an array."""
        return self._feed(np.zeros((), np.int32))[0].sharding

    def _feed(self, *arrays: np.ndarray) -> tuple:
        """Host arrays for a paged step: replicated over the mesh in one
        transfer (every chip reads the whole block table, tokens and
        positions), or as ``jnp.asarray`` makes them off-mesh.  A feed
        may alias its host array until the step has run, so no array
        passed here may change after the call."""
        import jax
        import jax.numpy as jnp
        if self.prepared.mesh is None:
            return tuple(jnp.asarray(a) for a in arrays)
        from jax.sharding import NamedSharding, PartitionSpec
        return tuple(jax.device_put(arrays, NamedSharding(
            self.prepared.mesh, PartitionSpec())))

    def kv_bytes(self) -> int:
        """HBM footprint of the block pools (the budget the scheduler
        manages, reported by serve.py and the benchmark).  Computed from
        abstract shapes — nothing is allocated, so calling this right
        before ``run()`` does not transiently double the cache's HBM."""
        import math

        import jax
        shapes = jax.eval_shape(self._init_caches)
        return sum(math.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(shapes))

    def dispatch_report(self):
        return self.prepared.dispatch_report()

    def run(self, requests: Sequence[Request], *, max_iters: Optional[int] = None,
            collect_tokens: bool = True) -> ServingReport:
        """Serve ``requests`` to completion.

        Each phase of the loop is a ``jax.profiler.TraceAnnotation``
        span, on the device trace's clock when a profiler trace is
        running (about a microsecond each when none is): ``engine.run``
        holds one ``engine.iter`` per work iteration, which holds
        ``engine.admit``, ``engine.prefill``, ``engine.decode_feed``,
        ``engine.dispatch`` and ``engine.sync``; ``engine.retire`` marks
        each retirement.  A decoding iteration's ``engine.sync`` comes
        after its dispatch and reads the previous step's tokens (its
        ``step``); an iteration that dispatches no decode step, or
        leaves no request running, reads everything at its end.  Spans
        of one request carry its ``rid``.
        """
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("engine.run", requests=len(requests)):
            return self._serve(requests, max_iters, collect_tokens)

    def _serve(self, requests, max_iters, collect_tokens) -> ServingReport:
        import jax
        import jax.numpy as jnp
        from jax.profiler import TraceAnnotation

        from repro.models.paged import (paged_decode_step, paged_prefill_chunk,
                                        reset_slot_state)

        spec = self.spec
        params = self.prepared.params
        sched = PagedScheduler(slots=spec.slots, table_width=spec.table_width,
                               num_blocks=self.num_blocks,
                               block_len=spec.block_len,
                               admission=spec.admission)
        caches = self._fresh_caches()
        # each slot's last token, on the device: the next decode's feed,
        # committed where a feed goes, as the token programs return it
        placement = self._feed_placement()
        tokens = jax.device_put(np.zeros((spec.slots, 1), np.int32),
                                placement)
        first_token, next_tokens = _token_programs(placement)
        arrivals = sorted(requests, key=lambda r: (r.arrival, r.rid))
        n = len(arrivals)
        if max_iters is None:
            # generous ceiling: every token its own iteration, plus slack
            # for queueing/preemption — a livelock trips this, not a hang
            max_iters = 64 + 16 * sum(
                len(r.prompt) + r.max_new_tokens for r in arrivals)
        stats: List[RequestStats] = []
        # token arrays not yet read: (tokens, [(slot, state)], decode
        # step that made them, 0 for a prefill's first token)
        pending = []
        finishing = {}    # rid -> iteration it retired in
        prefill_chunks = decode_calls = overlapped = 0
        ai = 0
        it = 0        # simulated clock (fast-forwards over idle gaps)
        work = 0      # iterations that had work — what the guard counts
        t0 = time.perf_counter()

        def _retire(s: int):
            with TraceAnnotation("engine.retire", rid=sched.slots[s].req.rid):
                st = sched.retire(s)
                finishing[st.req.rid] = it

        def _finish(st, now: float):
            lat = now - st.enqueue_wall
            stats.append(RequestStats(
                rid=st.req.rid, prompt_len=len(st.req.prompt),
                new_tokens=len(st.out),
                tokens=tuple(st.out) if collect_tokens else (),
                arrival=st.req.arrival, done_iter=finishing.pop(st.req.rid),
                latency_s=lat,
                queue_s=st.admit_wall - st.enqueue_wall,
                ttft_s=st.first_token_wall - st.enqueue_wall,
                tokens_per_s=len(st.out) / lat if lat > 0 else 0.0))

        def _produced(arr, entries, step=0):
            """``arr`` holds a new token of each ``(slot, state)``: start
            its copy to the host, read it later."""
            arr.copy_to_host_async()
            pending.append((arr, entries, step))

        def _sync(keep=None):
            """Read every pending token array but ``keep`` to the host;
            stamp first tokens, and finish the requests whose last token
            this was."""
            nonlocal pending
            read = [p for p in pending if p[0] is not keep]
            if not read:
                return
            pending = [p for p in pending if p[0] is keep]
            with TraceAnnotation("engine.sync",
                                 step=max(p[2] for p in read)):
                values = jax.device_get([p[0] for p in read])
            now = time.perf_counter()
            for (_, entries, _), v in zip(read, values):
                for s, st in entries:
                    st.out.append(int(v[s, 0]))
                    if st.first_token_wall is None:
                        st.first_token_wall = now
                    if (st.req.rid in finishing
                            and len(st.out) == st.emitted):
                        _finish(st, now)

        def _prefill():
            """One prefill chunk for the oldest prefilling request."""
            nonlocal caches, tokens, prefill_chunks
            pre = [s for s in sched.running
                   if sched.slots[s].state == "prefill"]
            if not pre:
                return
            s = min(pre, key=lambda s_: sched.slots[s_].seq)
            st = sched.slots[s]
            c = min(spec.prefill_chunk, len(st.req.prompt) - st.prefill_off)
            with TraceAnnotation("engine.prefill", rid=st.req.rid, tokens=c):
                if not sched.ensure_blocks(s, st.prefill_off + c - 1):
                    return
                # the table is copied: the step may run after the
                # scheduler has changed it
                tok, table = self._feed(np.asarray(
                    st.req.prompt[st.prefill_off:st.prefill_off + c],
                    np.int32)[None, :], sched.table[s:s + 1].copy())
                n_valid, slot = jnp.int32(c), jnp.int32(s)
                logits, caches = paged_prefill_chunk(
                    params, caches, tok, jnp.int32(st.prefill_off), table,
                    n_valid, slot, self.cfg, spec.block_len, spec.kv_qdtype)
                prefill_chunks += 1
                st.prefill_off += c
                if st.prefill_off < len(st.req.prompt):
                    return
                st.state = "decode"
                st.pos = len(st.req.prompt)
                tokens = first_token(tokens, logits, slot, n_valid)
                st.emitted = 1
                _produced(tokens, [(s, st)])
                if st.emitted >= st.req.max_new_tokens:
                    _retire(s)

        def _decode() -> bool:
            """One batched decode step over every decode-state slot;
            False when none was dispatched."""
            nonlocal caches, tokens, decode_calls, overlapped
            dec = [s for s in sched.running
                   if sched.slots[s].state == "decode"]
            if not dec:
                return False
            with TraceAnnotation("engine.decode_feed"):
                ready = []
                for s in dec:
                    st = sched.slots[s]
                    # an earlier ensure_blocks may have evicted this slot
                    if st is None or st.state != "decode":
                        continue
                    if sched.ensure_blocks(s, st.pos):
                        ready.append(s)
                # ...or a LATER one may have evicted an already-ready slot
                ready = [s for s in ready if sched.slots[s] is not None
                         and sched.slots[s].state == "decode"]
                if not ready:
                    return False
                positions = np.zeros((spec.slots,), np.int32)
                active = np.zeros((spec.slots,), bool)
                for s in ready:
                    positions[s] = sched.slots[s].pos
                    active[s] = True
                positions, table, active = self._feed(
                    positions, sched.table.copy(), active)
            with TraceAnnotation("engine.dispatch", slots=len(ready),
                                 step=decode_calls + 1):
                logits, caches = paged_decode_step(
                    params, caches, tokens, positions, table, active,
                    self.cfg, spec.block_len, spec.kv_qdtype)
                tokens = next_tokens(logits, tokens, active)
                decode_calls += 1
                if any(p[2] for p in pending):
                    overlapped += 1
            entries = [(s, sched.slots[s]) for s in ready]
            for s, st in entries:
                st.pos += 1
                st.emitted += 1
                if st.emitted >= st.req.max_new_tokens:
                    _retire(s)
            _produced(tokens, entries, decode_calls)
            # the previous step's tokens; all of them once none is running
            _sync(keep=tokens if sched.running else None)
            return True

        with self.prepared.activate():
            while len(stats) + len(finishing) < n:    # until all retire
                # guard on WORK iterations, not the simulated clock:
                # idle fast-forwarding jumps `it` to absolute arrival
                # timestamps, which a sparse trace can push past any
                # token-derived ceiling without a single wasted step
                if work >= max_iters:
                    raise RuntimeError(
                        f"engine made no progress after {max_iters} "
                        f"iterations ({len(stats)}/{n} done)")
                if not sched.has_work and arrivals[ai].arrival > it:
                    # idle: fast-forward to the next arrival
                    it = max(it + 1, int(np.ceil(arrivals[ai].arrival)))
                    continue
                with TraceAnnotation("engine.iter", it=it):
                    with TraceAnnotation("engine.admit"):
                        while ai < n and arrivals[ai].arrival <= it:
                            sched.enqueue(arrivals[ai],
                                          wall=time.perf_counter(),
                                          it=float(it))
                            ai += 1
                        for s in sched.admit_ready(wall=time.perf_counter()):
                            caches = reset_slot_state(caches, s)
                    _prefill()
                    if not _decode():
                        _sync()
                it += 1
                work += 1

        assert not pending and not finishing, (len(pending), finishing)
        return ServingReport(
            stats=sorted(stats, key=lambda s_: s_.rid),
            total=n, completed=len(stats),
            wall_s=time.perf_counter() - t0,
            model_calls=prefill_chunks + decode_calls,
            prefill_chunks=prefill_chunks, decode_calls=decode_calls,
            iterations=work,
            evictions=sched.evictions,
            max_blocks_in_use=sched.max_blocks_in_use,
            num_blocks=self.num_blocks,
            overlapped_steps=overlapped)
