"""The engine's one-step-late token read, on the CPU.

``Engine.run`` keeps each slot's last token on the device and reads a
decode step's tokens only after it has dispatched the next step.  That
must change nothing but when the host waits: the tokens, the schedule
(model calls, iterations, evictions, occupancy) and the iteration
clock are those of the synchronous loop it replaced, which
:func:`synchronous_serve` keeps here as the oracle; on one device and
on a ``(1, 4)`` mesh of four CPU devices (in a child process, as in
``tests/test_paged_mesh.py``).  A request's stamps are taken only once
its token is on the host, and a second run traces nothing.
"""

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import serving  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.models.paged import (paged_decode_step,  # noqa: E402
                                paged_prefill_chunk, reset_slot_state)
from repro.serving import engine as engine_mod  # noqa: E402
from repro.serving.scheduler import PagedScheduler, Request  # noqa: E402

HERE = Path(__file__).resolve().parent
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


def synchronous_serve(prepared, requests) -> dict:
    """The engine loop before the lagged read: every sampled token is
    read back to the host before the next model call."""
    spec, cfg, params = prepared.spec, prepared.cfg, prepared.params
    eng = serving.Engine(prepared)
    sched = PagedScheduler(slots=spec.slots, table_width=spec.table_width,
                           num_blocks=eng.num_blocks,
                           block_len=spec.block_len, admission=spec.admission)
    caches = eng._fresh_caches()
    arrivals = sorted(requests, key=lambda r: (r.arrival, r.rid))
    out = {}
    calls = {"prefill_chunks": 0, "decode_calls": 0, "iterations": 0}
    ai = it = 0

    def emit(s, token):
        st = sched.slots[s]
        st.out.append(token)
        if len(st.out) >= st.req.max_new_tokens:
            out[st.req.rid] = tuple(sched.retire(s).out)

    with prepared.activate():
        while len(out) < len(arrivals):
            if not sched.has_work and arrivals[ai].arrival > it:
                it = max(it + 1, int(np.ceil(arrivals[ai].arrival)))
                continue
            while ai < len(arrivals) and arrivals[ai].arrival <= it:
                sched.enqueue(arrivals[ai])
                ai += 1
            for s in sched.admit_ready():
                caches = reset_slot_state(caches, s)
            pre = [s for s in sched.running
                   if sched.slots[s].state == "prefill"]
            if pre:
                s = min(pre, key=lambda s_: sched.slots[s_].seq)
                st = sched.slots[s]
                c = min(spec.prefill_chunk, len(st.req.prompt) - st.prefill_off)
                if sched.ensure_blocks(s, st.prefill_off + c - 1):
                    tok, table = eng._feed(np.asarray(
                        st.req.prompt[st.prefill_off:st.prefill_off + c],
                        np.int32)[None, :], sched.table[s:s + 1].copy())
                    logits, caches = paged_prefill_chunk(
                        params, caches, tok, jnp.int32(st.prefill_off),
                        table, jnp.int32(c), jnp.int32(s), cfg,
                        spec.block_len, spec.kv_qdtype)
                    calls["prefill_chunks"] += 1
                    st.prefill_off += c
                    if st.prefill_off == len(st.req.prompt):
                        st.state, st.pos = "decode", len(st.req.prompt)
                        emit(s, int(jnp.argmax(logits[0, c - 1])))
            ready = []
            for s in [s for s in sched.running
                      if sched.slots[s].state == "decode"]:
                st = sched.slots[s]
                if (st is not None and st.state == "decode"
                        and sched.ensure_blocks(s, st.pos)):
                    ready.append(s)
            ready = [s for s in ready if sched.slots[s] is not None
                     and sched.slots[s].state == "decode"]
            if ready:
                feed = np.zeros((spec.slots, 1), np.int32)
                positions = np.zeros((spec.slots,), np.int32)
                active = np.zeros((spec.slots,), bool)
                for s in ready:
                    feed[s, 0] = sched.slots[s].out[-1]
                    positions[s] = sched.slots[s].pos
                    active[s] = True
                logits, caches = paged_decode_step(
                    params, caches,
                    *eng._feed(feed, positions, sched.table, active),
                    cfg, spec.block_len, spec.kv_qdtype)
                calls["decode_calls"] += 1
                nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
                for s in ready:
                    sched.slots[s].pos += 1
                    emit(s, int(nxt[s]))
            it += 1
            calls["iterations"] += 1
    generated = sum(len(t) for t in out.values())
    return dict(calls, tokens={str(r): list(t) for r, t in out.items()},
                evictions=sched.evictions,
                occupancy=(generated - len(out))
                / max(calls["decode_calls"] * spec.slots, 1))


def served(report, slots: int) -> dict:
    """What :func:`synchronous_serve` returns, from a ``ServingReport``."""
    return {"prefill_chunks": report.prefill_chunks,
            "decode_calls": report.decode_calls,
            "iterations": report.iterations,
            "tokens": {str(s.rid): list(s.tokens) for s in report.stats},
            "evictions": report.evictions,
            "occupancy": (report.generated_tokens - report.completed)
            / max(report.decode_calls * slots, 1)}


def _spec(**kw):
    base = dict(layout="dense", slots=3, max_len=64, block_len=8,
                prefill_chunk=8)
    base.update(kw)
    return serving.ServingSpec(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _spec().apply_to(get_smoke_config("internlm2_1_8b"))
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _trace(vocab, seed=0):
    return serving.make_poisson_trace(seed=seed, num_requests=8, rate=0.7,
                                      vocab_size=vocab)


WORKLOADS = {
    # Poisson arrivals through the default reserve admission
    "poisson": (dict(), lambda v: _trace(v)),
    # a pool too small for three slots: preemption and recompute
    "preempting": (dict(kv_blocks=4, admission="optimistic"), lambda v: [
        Request(rid=i, prompt=tuple(range(i + 1, i + 9)), max_new_tokens=8,
                arrival=float(i // 2)) for i in range(4)]),
    # one-token answers retire inside their prefill; a late arrival
    # leaves the engine idle in between
    "one_token_and_idle": (dict(), lambda v: [
        Request(rid=0, prompt=tuple(range(1, 13)), max_new_tokens=4),
        Request(rid=1, prompt=(3, 4, 5), max_new_tokens=1),
        Request(rid=2, prompt=(7, 8, 9, 10), max_new_tokens=3),
        Request(rid=3, prompt=(6, 2), max_new_tokens=1, arrival=300.0),
        Request(rid=4, prompt=tuple(range(2, 14)), max_new_tokens=2,
                arrival=300.0)]),
}


@pytest.mark.parametrize("workload", sorted(WORKLOADS))
def test_lagged_engine_serves_what_the_synchronous_loop_served(
        model, workload):
    cfg, params = model
    kw, make = WORKLOADS[workload]
    prepared = serving.prepare(params, _spec(**kw), cfg=cfg)
    reqs = make(cfg.vocab_size)
    report = serving.Engine(prepared).run(reqs)
    want = synchronous_serve(prepared, reqs)
    assert served(report, prepared.spec.slots) == want
    assert report.completed == len(reqs)
    assert [s.new_tokens for s in report.stats] == [
        r.max_new_tokens for r in sorted(reqs, key=lambda r: r.rid)]
    if workload == "preempting":
        assert report.evictions > 0


def test_feeds_never_change_after_they_are_fed(model, monkeypatch):
    # a step may run after the host has moved on: what it was fed must
    # not be the scheduler's table, which retirement and eviction rewrite
    cfg, params = model
    kw, make = WORKLOADS["preempting"]
    prepared = serving.prepare(params, _spec(**kw), cfg=cfg)
    fed, feed = [], serving.Engine._feed

    def recording(self, *arrays):
        fed.extend((a, a.copy()) for a in arrays)
        return feed(self, *arrays)

    monkeypatch.setattr(serving.Engine, "_feed", recording)
    report = serving.Engine(prepared).run(make(cfg.vocab_size))
    assert report.evictions > 0 and len(fed) > 3 * report.decode_calls
    assert all(np.array_equal(a, was) for a, was in fed)


def _count_compiles(fn):
    compiles = [0]

    def on(event, duration, **_):
        if event in COMPILE_EVENTS:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        return fn(), compiles[0]
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


@pytest.mark.parametrize("placed", [False, True],
                         ids=["default_device", "committed_params"])
def test_run_after_warm_up_compiles_nothing(model, placed):
    # a warm-up as the benchmark's: one request per prefill chunk length;
    # then other traffic, whose first prompt may end on any chunk length
    cfg, params = model
    if placed:
        params = jax.device_put(params, jax.devices()[0])
    prepared = serving.prepare(params, _spec(), cfg=cfg)
    eng = serving.Engine(prepared)
    eng.run([Request(rid=c, prompt=tuple(range(1, c + 1)), max_new_tokens=2)
             for c in range(1, prepared.spec.prefill_chunk + 1)])
    reqs = _trace(cfg.vocab_size, seed=2)
    report, compiles = _count_compiles(lambda: eng.run(reqs))
    assert compiles == 0
    assert report.completed == len(reqs)
    again, compiles = _count_compiles(lambda: eng.run(reqs[::-1]))
    assert compiles == 0
    assert [s.tokens for s in again.stats] == [s.tokens for s in report.stats]


class _Clock:
    """A clock that ticks once per reading, standing in for both the
    engine's ``time.perf_counter`` and its trace spans: every stamp and
    span edge is a distinct tick, in program order."""

    def __init__(self):
        self.t = 0.0
        self.spans = []

    def perf_counter(self) -> float:
        self.t += 1.0
        return self.t

    def annotation(self, name, **stats):
        clock = self

        class _Span:
            def __enter__(self):
                self.name, self.stats = name, stats
                self.start, self.end = clock.perf_counter(), None
                clock.spans.append(self)
                return self

            def __exit__(self, *exc):
                self.end = clock.perf_counter()

        return _Span()

    def named(self, name):
        return [s for s in self.spans if s.name == name]


def _clocked_run(prepared, reqs, monkeypatch):
    clock, enqueued = _Clock(), {}

    class Sched(PagedScheduler):
        def enqueue(self, req, *, wall=0.0, it=0.0):
            enqueued[req.rid] = wall
            super().enqueue(req, wall=wall, it=it)

    monkeypatch.setattr(engine_mod, "time",
                        types.SimpleNamespace(perf_counter=clock.perf_counter))
    monkeypatch.setattr(engine_mod, "PagedScheduler", Sched)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", clock.annotation)
    return serving.Engine(prepared).run(reqs), clock, enqueued


def test_stamps_follow_the_sync_that_read_the_token(model, monkeypatch):
    cfg, params = model
    prepared = serving.prepare(params, _spec(), cfg=cfg)
    _, make = WORKLOADS["one_token_and_idle"]
    report, clock, enqueued = _clocked_run(prepared, make(cfg.vocab_size),
                                           monkeypatch)
    syncs = clock.named("engine.sync")

    def read_by(after, step):
        """The first sync after tick ``after`` that reads decode step
        ``step`` (0: a prefill's first token, which every sync reads)."""
        return next(s for s in syncs
                    if s.start > after and s.stats["step"] >= step)

    for st in report.stats:
        (retire,) = [s for s in clock.named("engine.retire")
                     if s.stats["rid"] == st.rid]
        last_prefill = [s for s in clock.named("engine.prefill")
                        if s.stats["rid"] == st.rid][-1]
        made = [s for s in clock.named("engine.dispatch")
                if s.start < retire.start]
        if st.new_tokens == 1:      # its only token came from the prefill
            assert retire.start < last_prefill.end
            last = read_by(last_prefill.end, 0)
        else:
            last = read_by(retire.end, made[-1].stats["step"])
        first = read_by(last_prefill.end, 0)
        # each stamp is the tick right after the sync that read it
        t0 = enqueued[st.rid]
        assert t0 + st.ttft_s == first.end + 1, st
        assert t0 + st.latency_s == last.end + 1, st


def test_overlapped_steps_are_every_step_but_the_first(model, monkeypatch):
    cfg, params = model
    prepared = serving.prepare(params, _spec(slots=2), cfg=cfg)
    # two slots always hold a decoding request: no idle iteration
    reqs = [Request(rid=0, prompt=(5, 6, 7, 8, 9), max_new_tokens=6),
            Request(rid=1, prompt=(3, 4, 5), max_new_tokens=3),
            Request(rid=2, prompt=(7, 8, 9, 10), max_new_tokens=4)]
    report, clock, _ = _clocked_run(prepared, reqs, monkeypatch)
    iters = [s.stats["it"] for s in clock.named("engine.iter")]
    assert iters == list(range(len(iters)))
    assert report.decode_calls == 6
    assert report.overlapped_steps == report.decode_calls - 1
    assert "5/6 decode steps overlapped" in report.describe()
    # a second run counts its own first step again
    again = serving.Engine(prepared).run(reqs)
    assert again.overlapped_steps == report.overlapped_steps


CHILD = r"""
import dataclasses, json, sys

import jax
import jax.monitoring as mon

sys.path.insert(0, sys.argv[1])
from test_engine_lag import COMPILE_EVENTS, served, synchronous_serve

from repro import serving
from repro.configs import get_smoke_config
from repro.models import init_params

# Mistral-Large's head layout at a tiny width: 8 query heads on 4 KV heads
cfg0 = dataclasses.replace(get_smoke_config("mistral_large_123b"),
                           num_heads=8, num_kv_heads=4)
spec = serving.ServingSpec(layout="compressed", sparsity=(2, 4), mesh=(1, 4),
                           backend="jnp", slots=4, max_len=64, block_len=8,
                           prefill_chunk=16)
cfg = spec.apply_to(cfg0)
prepared = serving.prepare(init_params(jax.random.PRNGKey(0), cfg), spec,
                           cfg=cfg)
reqs = serving.make_poisson_trace(seed=3, num_requests=8, rate=1.0,
                                  vocab_size=cfg.vocab_size)
eng = serving.Engine(prepared)
eng.run(reqs)
compiles = [0]


def on(event, duration, **_):
    if event in COMPILE_EVENTS:
        compiles[0] += 1


mon.register_event_duration_secs_listener(on)
again = eng.run(reqs)
mon.unregister_event_duration_listener(on)
print(json.dumps({"devices": len(prepared.mesh.devices.flat),
                  "compiles_second_run": compiles[0],
                  "served": served(again, spec.slots),
                  "synchronous": synchronous_serve(prepared, reqs)}))
"""


@pytest.fixture(scope="module")
def on_mesh():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(HERE.parent / "src"))
    r = subprocess.run([sys.executable, "-c", CHILD, str(HERE)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_on_a_mesh_lagged_engine_serves_what_the_synchronous_loop_served(
        on_mesh):
    assert on_mesh["devices"] == 4
    assert on_mesh["served"] == on_mesh["synchronous"]
    assert on_mesh["served"]["decode_calls"] > 0


def test_on_a_mesh_second_run_compiles_nothing(on_mesh):
    assert on_mesh["compiles_second_run"] == 0
