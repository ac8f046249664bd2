"""The engine-span reduction (``bench/spans.py``) on the CPU: nesting,
self time, idle put to the innermost span by overlap and the bucket for
idle outside every span, on hand-made events and on a trace recorded on
a TPU v5e (``fixtures/engine_spans_2of4.xplane.pb``, made by
``bench/record_trace_fixture.py`` from a program with the engine spans
and named kernels: one 32-token prompt through internlm2-1.8B 2:4, one
prefill chunk and two decode steps).  Also the readers of the span and
queue metrics, and the kernel names' place in a Pallas call's text."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import counting, spans, trace
from bench.harness import HERE

FIXTURE = Path(__file__).resolve().parent / "fixtures"
KERNELS = HERE / "kernels"
PEAK = counting.peaks("TPU v5 lite")


def _reader(name):
    from bench.harness import _load_module
    return _load_module(HERE / "metrics" / f"{name}.py", f"test_{name}")


# one iteration of the loop on the interpreter's line, a Python frame
# inside the sync, and a thread that is not the interpreter's
HOST = [("python3", "engine.run", 0, 1000),
        ("python3", "engine.iter", 10, 400),
        ("python3", "engine.admit", 10, 20),
        ("python3", "engine.decode_feed", 40, 60),
        ("python3", "engine.dispatch", 100, 100),
        ("python3", "engine.sync", 200, 150),
        ("python3", "$array.py:631 _value", 210, 100),
        ("python3", "engine.retire", 360, 30),
        ("main/291", "ReadSyncFlag", 220, 10)]
# busy 150-340 and 600-700: idle 0-150, 340-600, 700-1000
OPS = [trace.Op("a", 150, 100), trace.Op("b", 240, 100),
       trace.Op("c", 600, 100)]


def test_spans_nest_by_containment_with_self_time():
    got = spans.nest(HOST)
    assert [s.name for s in got] == ["engine.run", "engine.iter",
                                     "engine.admit", "engine.decode_feed",
                                     "engine.dispatch", "engine.sync",
                                     "engine.retire"]
    by = {s.name: s for s in got}
    assert by["engine.run"].parent is None
    assert by["engine.iter"].parent is by["engine.run"]
    assert all(by[n].parent is by["engine.iter"] for n in
               ("engine.admit", "engine.decode_feed", "engine.dispatch",
                "engine.sync", "engine.retire"))
    assert by["engine.run"].self_ns == 600
    assert by["engine.iter"].self_ns == 400 - (20 + 60 + 100 + 150 + 30)
    assert by["engine.sync"].self_ns == 150


def test_idle_goes_to_the_innermost_span_by_overlap():
    red = spans.reduce_spans([("/device:TPU:0", OPS, [])], HOST)
    idle = dict(red.idle_by_span)
    # 0-150: run 10, admit 20, iter 10, feed 60, dispatch 50;
    # 340-600: sync 10, iter 10 + 20, retire 30, run 190; 700-1000: run
    assert idle == {"engine.run": pytest.approx(500e-9),
                    "engine.decode_feed": pytest.approx(60e-9),
                    "engine.dispatch": pytest.approx(50e-9),
                    "engine.iter": pytest.approx(40e-9),
                    "engine.retire": pytest.approx(30e-9),
                    "engine.admit": pytest.approx(20e-9),
                    "engine.sync": pytest.approx(10e-9)}
    assert [n for n, _ in red.idle_by_span][0] == "engine.run"
    # every idle nanosecond of the window is put somewhere, once
    assert red.window_s == pytest.approx(1000e-9)
    assert red.idle_s == pytest.approx(red.window_s - 290e-9)
    assert red.engine_idle_s() == pytest.approx(700e-9)
    # the iteration's own time: its 400 ns less the 150 ns sync
    assert red.host_loop_ms() == pytest.approx(250e-6)


def test_idle_outside_every_span_and_several_devices():
    host = [("python3", "engine.run", 0, 100),
            ("python3", "engine.run", 200, 100)]
    quiet = spans.reduce_spans([("/device:TPU:0", [], [])], host)
    assert dict(quiet.idle_by_span) == {
        "engine.run": pytest.approx(200e-9),
        spans.OUTSIDE: pytest.approx(100e-9)}
    assert quiet.engine_idle_s() == pytest.approx(200e-9)
    # a traced window timed from before the profiler started: the
    # stretch before the first span is idle outside them too
    wide = spans.reduce_spans([("/device:TPU:0", [], [])], host,
                              window_s=400e-9)
    assert wide.window_s == pytest.approx(400e-9)
    assert dict(wide.idle_by_span) == {
        "engine.run": pytest.approx(200e-9),
        spans.OUTSIDE: pytest.approx(200e-9)}
    # the mean over the devices that ran anything, as busy time is
    two = spans.reduce_spans([("/device:TPU:0", [trace.Op("a", 0, 300)], []),
                              ("/device:TPU:1", [trace.Op("b", 0, 100)], []),
                              ("/device:TPU:2", [], [])], host)
    assert dict(two.idle_by_span) == {
        "engine.run": pytest.approx(50e-9),
        spans.OUTSIDE: pytest.approx(50e-9)}


def test_a_sync_nested_deeper_still_leaves_the_loop():
    host = [("python3", "engine.iter", 0, 100),
            ("python3", "engine.prefill", 0, 60),
            ("python3", "engine.sync", 30, 20),
            ("python3", "engine.sync", 70, 20),
            ("python3", "engine.iter", 100, 50)]
    red = spans.reduce_spans([], host)
    assert red.host_loop_ms() == pytest.approx(1e-6 * (60 + 50) / 2)
    assert spans.reduce_spans([], [("python3", "other", 0, 5)]) is None


def test_readers_of_the_span_and_queue_metrics():
    red = spans.reduce_spans([("/device:TPU:0", OPS, [])], HOST)
    obs = SimpleNamespace(spans=red, trace=SimpleNamespace(window_s=2e-6),
                          segments=[SimpleNamespace(stats=[
                              SimpleNamespace(queue_s=float(q))
                              for q in range(21)])])
    assert _reader("host_loop_ms").read(obs) == pytest.approx(250e-6)
    assert _reader("engine_idle_pct").read(obs) == pytest.approx(35.0)
    assert _reader("queue_wait_p95_s").read(obs) == pytest.approx(19.0)
    # a program without spans or stamps: nothing to read, no error
    bare = SimpleNamespace(trace=None, segments=[SimpleNamespace(
        stats=[SimpleNamespace(latency_s=1.0)])])
    for name in ("host_loop_ms", "engine_idle_pct", "queue_wait_p95_s"):
        assert _reader(name).read(bare) is None


# a fused 2:4 gate-up call as a v5e trace names it once the kernels
# carry their names: the custom call is named after the kernel, and its
# metadata names the registry entry (the text holds newlines there)
NAMED_LINEAR = (
    "%nm_spmm_dual.74 = bf16[16,8192]{1,0:T(8,128)(2,1)S(1)} custom-call("
    "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %fusion.84, "
    "bf16[1024,8192]{1,0:T(8,128)(2,1)S(1)} %d.42, "
    "u8[256,8192]{1,0:T(8,128)(4,1)S(1)} %d.43, "
    "bf16[1024,8192]{1,0:T(8,128)(2,1)S(1)} %d.44, "
    "u8[256,8192]{1,0:T(8,128)(4,1)S(1)} %d.45), "
    "custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={bf16[16,2048]{1,0}, "
    "bf16[1024,8192]{1,0}, u8[256,8192]{1,0}, bf16[1024,8192]{1,0}, "
    "u8[256,8192]{1,0}}, "
    "frontend_attributes={kernel_metadata={\n\"kernel\":\"nm_spmm\"\n}}")


def test_named_linear_is_claimed_by_one_class_and_counted_the_same():
    classes = trace.load_classes(KERNELS)
    op = trace.Op(NAMED_LINEAR, 0, 1000)
    assert trace.classify(op, classes)["class"] == "linear"
    assert trace.linear_counts(NAMED_LINEAR) == (
        2 * 16 * 2 * 1024 * 8192,
        16 * 2048 * 2 + 2 * (1024 * 8192 * 2 + 256 * 8192) + 16 * 8192 * 2)
    red = trace.reduce_ops([("/device:TPU:0", [op], [])], [], 1e-5,
                           classes, PEAK)
    assert red.linear_calls == 1


@pytest.fixture(scope="module")
def recorded():
    meta = json.loads((FIXTURE / "engine_spans_2of4.json").read_text())
    devices, host = trace.load_ops(FIXTURE / "engine_spans_2of4.xplane.pb")
    return devices, host, meta


def test_recorded_spans_reduce(recorded):
    devices, host, meta = recorded
    red = spans.reduce_spans(devices, host)
    names = {s.name for s in red.spans}
    assert names == {"engine.run", "engine.iter", "engine.admit",
                     "engine.prefill", "engine.decode_feed",
                     "engine.dispatch", "engine.sync", "engine.retire"}
    assert all(s.line.startswith("python") for s in red.spans)
    # one request: every iteration runs one decode step
    assert len(red.named("engine.iter")) == meta["decode_calls"]
    assert len(red.named("engine.dispatch")) == meta["decode_calls"]
    assert len(red.named("engine.prefill")) == meta["prefill_chunks"]
    assert len(red.named("engine.sync")) == (meta["decode_calls"]
                                            + meta["prefill_chunks"])
    (run,) = red.named("engine.run")
    assert all(s is run or s.parent is not None for s in red.spans)
    # idle in the window, all put somewhere: the window less busy time
    _, ops, _ = devices[0]
    inside = [o for o in ops if run.start_ns <= o.start_ns
              and o.start_ns + o.dur_ns <= run.end_ns]
    busy, _ = trace.busy_union(inside)
    assert red.idle_s == pytest.approx(red.window_s - busy, rel=1e-3)
    assert sum(s for _, s in red.idle_by_span) == pytest.approx(red.idle_s)
    assert 0 < red.engine_idle_s() <= red.idle_s
    assert 0 < red.host_loop_ms() < 1e3 * red.window_s


def test_recorded_kernels_carry_their_names(recorded):
    devices, host, _ = recorded
    _, ops, mods = devices[0]
    pallas = [o for o in ops if trace.PALLAS in o.name]
    assert pallas
    assert all('"kernel":"nm_spmm"' in o.name for o in pallas)
    # the same reduction as before: every kernel claimed once, as linear
    first = min(o.start_ns for o in ops)
    last = max(o.start_ns + o.dur_ns for o in ops)
    red = trace.reduce_ops(devices, host, (last - first) * 1e-9,
                           trace.load_classes(KERNELS), PEAK)
    assert red.linear_calls == len(pallas) == 24 * 6 * 3
