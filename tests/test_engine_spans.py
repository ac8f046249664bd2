"""The serving engine's trace spans and request stamps, on the CPU.

``Engine.run`` marks each phase of its loop with a
``jax.profiler.TraceAnnotation`` (``engine.run`` > ``engine.iter`` >
``engine.admit`` / ``engine.prefill`` / ``engine.decode_feed`` /
``engine.dispatch`` / ``engine.sync`` / ``engine.retire``).  A run under
``jax.profiler.trace`` must hold every span, one ``engine.iter`` per
work iteration the report counts, and exactly one ``engine.sync`` after
the ``engine.dispatch`` of each decoding iteration, which reads the
previous step's tokens.  Each request's stamps must read
``0 <= queue_s <= ttft_s <= latency_s``, also when preemption makes it
prefill twice.
"""

import tempfile
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro import serving  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.serving.scheduler import PagedScheduler, Request  # noqa: E402

SPANS = {"engine.run", "engine.iter", "engine.admit", "engine.prefill",
         "engine.decode_feed", "engine.dispatch", "engine.sync",
         "engine.retire"}


def _spec(**kw):
    base = dict(layout="dense", slots=2, max_len=64, block_len=8,
                prefill_chunk=8)
    base.update(kw)
    return serving.ServingSpec(**base)


@pytest.fixture(scope="module")
def prepared():
    spec = _spec()
    cfg = spec.apply_to(get_smoke_config("internlm2_1_8b"))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return serving.prepare(params, spec, cfg=cfg)


def _requests():
    # prompts of 12 take two prefill chunks of 8; three requests on two
    # slots queue; the late one makes the engine idle, then fast-forward
    return [Request(rid=0, prompt=tuple(range(1, 13)), max_new_tokens=4),
            Request(rid=1, prompt=(3, 4, 5), max_new_tokens=1),
            Request(rid=2, prompt=(7, 8, 9, 10), max_new_tokens=3),
            Request(rid=3, prompt=tuple(range(2, 14)), max_new_tokens=2,
                    arrival=500.0)]


class _Span:
    def __init__(self, name, start, dur, stats):
        self.name, self.start, self.end = name, start, start + dur
        self.stats = stats
        self.parent = None
        self.children = []


def _engine_spans(trace_dir):
    """The ``engine.*`` events of the trace, nested by containment."""
    from jax.profiler import ProfileData

    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.append(_Span(e.name, e.start_ns, e.duration_ns,
                                       dict(e.stats)))
    spans.sort(key=lambda s: (s.start, -s.end))
    stack = []
    for s in spans:
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if stack:
            assert s.end <= stack[-1].end, "spans overlap without nesting"
            s.parent = stack[-1]
            s.parent.children.append(s)
        stack.append(s)
    return spans


@pytest.fixture(scope="module")
def traced(prepared):
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            report = serving.Engine(prepared).run(_requests())
        return report, _engine_spans(d)


def _named(spans, name):
    return [s for s in spans if s.name == name]


def test_every_span_appears_and_nests(traced):
    report, spans = traced
    assert report.completed == 4
    assert {s.name for s in spans} == SPANS
    (run,) = _named(spans, "engine.run")
    assert run.parent is None and run.stats["requests"] == 4
    for s in spans:
        if s.name == "engine.iter":
            assert s.parent is run
        elif s.name != "engine.run":
            assert s.parent is not None and s.parent is not run, s.name


def test_one_iter_span_per_work_iteration(traced):
    report, spans = traced
    iters = _named(spans, "engine.iter")
    assert len(iters) == report.iterations
    assert report.iterations < report.stats[-1].done_iter  # idle skipped
    # the stat is the engine's clock: it jumps over the idle stretch
    clocks = [s.stats["it"] for s in iters]
    assert clocks == sorted(clocks) and clocks[-1] >= 500
    assert len(_named(spans, "engine.dispatch")) == report.decode_calls
    assert len(_named(spans, "engine.prefill")) == report.prefill_chunks
    assert all(s.parent.name == "engine.iter"
               for s in _named(spans, "engine.admit"))


def test_each_decoding_iteration_syncs_once_after_dispatch(traced):
    report, spans = traced
    iters = _named(spans, "engine.iter")
    decoding = 0
    for i, it in enumerate(iters):
        kids = [c.name for c in it.children]
        if "engine.dispatch" not in kids:
            # nothing dispatched: what is pending is read at the end
            assert kids.count("engine.sync") <= 1
            assert "engine.sync" not in kids[:-1]
            continue
        decoding += 1
        assert kids.count("engine.dispatch") == 1
        assert kids.count("engine.sync") == 1
        assert kids.count("engine.decode_feed") == 1
        # feed, then dispatch, then the host reads the previous step
        order = [k for k in kids if k in ("engine.decode_feed",
                                          "engine.dispatch", "engine.sync")]
        assert order == ["engine.decode_feed", "engine.dispatch",
                         "engine.sync"]
        (dispatch,) = [c for c in it.children if c.name == "engine.dispatch"]
        (sync,) = [c for c in it.children if c.name == "engine.sync"]
        # ...and this step's too where none is left running: before the
        # idle stretch and at the end of the run
        drains = (i + 1 == len(iters)
                  or iters[i + 1].stats["it"] > it.stats["it"] + 1)
        lag = 0 if drains else 1
        assert sync.stats["step"] == dispatch.stats["step"] - lag
    assert decoding == report.decode_calls
    # a prefill never waits: its first token is read with the next sync
    assert all(s.parent.name == "engine.iter"
               for s in _named(spans, "engine.sync"))


def test_spans_of_a_request_carry_its_rid(traced):
    report, spans = traced
    retired = sorted(s.stats["rid"] for s in _named(spans, "engine.retire"))
    assert retired == [s.rid for s in report.stats]
    chunks = {}
    for s in _named(spans, "engine.prefill"):
        chunks.setdefault(s.stats["rid"], []).append(s.stats["tokens"])
    assert chunks == {0: [8, 4], 1: [3], 2: [4], 3: [8, 4]}
    # a one-token answer retires inside its prefill
    (r1,) = [s for s in _named(spans, "engine.retire")
             if s.stats["rid"] == 1]
    assert r1.parent.name == "engine.prefill"
    assert all(s.stats["slots"] >= 1
               for s in _named(spans, "engine.dispatch"))


def _assert_stamps_ordered(report):
    for s in report.stats:
        assert 0.0 <= s.queue_s <= s.ttft_s <= s.latency_s, s


def test_request_stamps_are_ordered(traced, prepared):
    report, _ = traced
    _assert_stamps_ordered(report)
    by_rid = {s.rid: s for s in report.stats}
    # two slots: rid 2 waits for a retirement before it is admitted
    assert by_rid[2].queue_s > by_rid[0].queue_s
    assert "p95 queue" in report.describe()
    _assert_stamps_ordered(serving.run_lockstep(prepared, _requests()[:3]))


def test_request_stamps_survive_preemption(prepared):
    reqs = [Request(rid=i, prompt=(5, 9, 13, 2, 11, 3, 8, 4),
                    max_new_tokens=8) for i in range(3)]
    tight = serving.prepare(
        prepared.params, _spec(kv_blocks=3, admission="optimistic"),
        cfg=prepared.cfg)
    report = serving.Engine(tight).run(reqs)
    assert report.completed == 3 and report.evictions > 0
    _assert_stamps_ordered(report)


def test_scheduler_keeps_first_stamps_across_preemption():
    sched = PagedScheduler(slots=2, table_width=4, num_blocks=3,
                           block_len=4, admission="optimistic")
    for rid in range(2):
        sched.enqueue(Request(rid=rid, prompt=tuple(range(1, 9)),
                              max_new_tokens=2), wall=0.5)
    assert sched.admit_ready(wall=1.0) == [0, 1]
    sched.slots[1].first_token_wall = 2.0
    assert sched.ensure_blocks(0, 7)
    assert not sched.ensure_blocks(1, 7)   # slot 1 is the LIFO victim
    sched.retire(0)
    assert sched.admit_ready(wall=3.0) == [0]
    st = sched.slots[0]
    assert st.req.rid == 1 and st.out == []
    assert (st.enqueue_wall, st.admit_wall, st.first_token_wall) == (
        0.5, 1.0, 2.0)
