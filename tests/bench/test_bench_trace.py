"""The trace reduction on the CPU: busy union, idle share, program time,
kernel classes and linear-call counts, on hand-made events and on a
small trace recorded on a TPU v5e (``fixtures/decode_2of4.xplane.pb``,
made by ``bench/record_trace_fixture.py``: one 32-token prompt through
internlm2-1.8B 2:4 served with 16 slots of 256 positions and 128-token
prefill chunks, one prefill chunk and two decode steps)."""

import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import counting, trace
from bench.harness import HERE, Observed, _load_module, load_cell

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixtures"
KERNELS = HERE / "kernels"
PEAK = counting.peaks("TPU v5 lite")

# a fused 2:4 gate-up call as a v5e trace names it
LINEAR = ("%closed_call.74 = bf16[16,8192]{1,0:T(8,128)(2,1)S(1)} custom-call("
          "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %fusion.84, "
          "bf16[1024,8192]{1,0:T(8,128)(2,1)S(1)} %d.42, "
          "u8[256,8192]{1,0:T(8,128)(4,1)S(1)} %d.43, "
          "bf16[1024,8192]{1,0:T(8,128)(2,1)S(1)} %d.44, "
          "u8[256,8192]{1,0:T(8,128)(4,1)S(1)} %d.45), "
          "custom_call_target=\"tpu_custom_call\", "
          "operand_layout_constraints={bf16[16,2048]{1,0}, "
          "bf16[1024,8192]{1,0}, u8[256,8192]{1,0}, bf16[1024,8192]{1,0}, "
          "u8[256,8192]{1,0}}, frontend_attributes={kernel_metadata={}}")
FUSION = ("%fusion.3 = bf16[16,2048]{1,0} fusion(bf16[16,2048]{1,0} %a), "
          "kind=kLoop, calls=%fused_computation.3")
WHILE = ("%while.13 = (s32[], bf16[16,1,2048]) "
         "while((s32[], bf16[16,1,2048]) %t)")


def op(name, start, dur):
    return trace.Op(name, start, dur)


def test_busy_union_merges_overlaps_and_finds_gaps():
    ops = [op("a", 0, 10), op("b", 5, 10), op("c", 30, 5), op("d", 31, 1)]
    busy, gaps = trace.busy_union(ops)
    assert busy == pytest.approx(20e-9)
    assert gaps == [(15, 30)]


def test_linear_counts_from_hlo_shapes():
    flops, nbytes = trace.linear_counts(LINEAR)
    # two (1024, 8192) value operands (2:4 of 2048 inputs), 16 rows
    assert flops == 2 * 16 * 2 * 1024 * 8192
    assert nbytes == (16 * 2048 * 2 + 2 * (1024 * 8192 * 2 + 256 * 8192)
                      + 16 * 8192 * 2)
    # the same call counted from the configuration's shapes
    assert counting.kernel_call(2048, 8192, 16, "compressed", (2, 4),
                                weights=2) == (flops, nbytes)


def test_reduce_classifies_kernels_and_programs():
    ops = [op(WHILE, 0, 4000), op(LINEAR, 0, 1000), op(FUSION, 1000, 500),
           op(LINEAR, 3000, 1000)]
    mods = [op("jit_paged_decode_step(7880572870544329937)", 0, 4000)]
    red = trace.reduce_ops([("/device:TPU:0", ops, mods)], [], 1e-5,
                           trace.load_classes(KERNELS), PEAK)
    assert red.busy_s == pytest.approx(4e-6)      # the while covers all
    assert red.linear_calls == 2 and red.linear_s == pytest.approx(2e-6)
    assert red.programs == {"paged_decode_step": [pytest.approx(4e-6)]}
    f, b = trace.linear_counts(LINEAR)
    assert red.linear_least_s == pytest.approx(
        2 * counting.least_time(f, b, PEAK))
    # a container's time is its body's: the breakdown leaves it out
    assert [n for n, _ in red.breakdown()["device_ops"]] == ["linear",
                                                             "fusion"]
    assert red.idle_gaps == []


def test_idle_gap_named_by_the_python_thread():
    ops = [op(LINEAR, 0, 1000), op(LINEAR, 3000, 1000)]
    host = [("python", "$engine.py:151 run", -10, 10_000),
            ("python", "$array.py:631 _value", 1600, 1000),
            ("main/291", "ReadSyncFlag", 2000, 100)]
    red = trace.reduce_ops([("/device:TPU:0", ops, [])], host, 1e-5,
                           trace.load_classes(KERNELS), PEAK)
    assert red.busy_s == pytest.approx(2e-6)
    assert red.idle_gaps == [("python: $array.py:631 _value",
                              pytest.approx(2e-6))]


def test_unknown_pallas_kernel_fails_the_reduction():
    attn = ("%closed_call.9 = bf16[1,8,2,512,128]{4,3,2,1,0} custom-call("
            "bf16[1,8,2,512,128]{4,3,2,1,0} %q, bf16[1,512,8,128]{3,2,1,0} "
            "%k), custom_call_target=\"tpu_custom_call\"")
    ops = [op(attn, 0, 10)]
    # an attention kernel's operands have more than two axes: no class
    # claims it, whatever classes there are
    with pytest.raises(trace.TraceError, match="no class"):
        trace.reduce_ops([("/device:TPU:0", ops, [])], [], 1e-6,
                         trace.load_classes(KERNELS), PEAK)
    with pytest.raises(trace.TraceError, match="no class"):
        trace.reduce_ops([("/device:TPU:0", ops, [])], [], 1e-6, [], PEAK)


def test_kernel_claimed_by_two_classes_fails(tmp_path):
    for name in ("linear", "other"):
        (tmp_path / f"{name}.json").write_text(json.dumps(
            {"class": name, "match": "tpu_custom_call", "why": "test"}))
    with pytest.raises(trace.TraceError, match="linear, other"):
        trace.reduce_ops([("/device:TPU:0", [op(LINEAR, 0, 10)], [])], [],
                         1e-6, trace.load_classes(tmp_path), PEAK)


def test_linear_class_claims_only_linear_shapes():
    classes = trace.load_classes(KERNELS)
    assert trace.classify(op(LINEAR, 0, 1), classes)["class"] == "linear"
    assert trace.classify(op(FUSION, 0, 1), classes) is None
    # two axes everywhere, but the rows in and out differ: claimed by
    # its look, refused by its counts
    odd = LINEAR.replace("bf16[16,8192]{1,0:T(8,128)(2,1)S(1)} custom",
                         "bf16[8,8192]{1,0:T(8,128)(2,1)S(1)} custom")
    assert trace.classify(op(odd, 0, 1), classes)["class"] == "linear"
    with pytest.raises(trace.TraceError, match="not a linear"):
        trace.reduce_ops([("/device:TPU:0", [op(odd, 0, 10)], [])], [],
                         1e-6, classes, PEAK)


@pytest.fixture(scope="module")
def recorded():
    path = FIXTURE / "decode_2of4.xplane.pb"
    meta = json.loads((FIXTURE / "decode_2of4.json").read_text())
    devices, host = trace.load_ops(path)
    return devices, host, meta


def test_recorded_trace_planes(recorded):
    devices, host, meta = recorded
    assert meta["device_kind"] == "TPU v5 lite"
    assert [name for name, _, _ in devices] == ["/device:TPU:0"]
    _, ops, mods = devices[0]
    assert ops and mods and host
    assert any(line.startswith("python") for line, *_ in host)


def test_recorded_trace_reduction(recorded):
    devices, host, meta = recorded
    _, ops, mods = devices[0]
    first = min(o.start_ns for o in ops)
    last = max(o.start_ns + o.dur_ns for o in ops)
    red = trace.reduce_ops(devices, host, (last - first) * 1e-9,
                           trace.load_classes(KERNELS), PEAK)
    # every program the engine ran is there, by name
    assert len(red.programs["paged_decode_step"]) == meta["decode_calls"]
    assert len(red.programs["paged_prefill_chunk"]) == \
        meta["prefill_chunks"]
    # 24 layers x (q, k, v, o, fused gate-up, down) per model call
    calls = meta["decode_calls"] + meta["prefill_chunks"]
    assert red.linear_calls == 24 * 6 * calls
    # busy is a union: at most the window, at least the longest op
    assert max(o.dur_ns for o in ops) * 1e-9 <= red.busy_s <= red.window_s
    assert 0 < red.linear_s <= red.busy_s
    # a share of the roofline can never pass 100%
    assert 0 < red.linear_least_s < red.linear_s
    assert red.breakdown()["device_ops"][0][0] == "linear"
    assert all(n.split(": ")[0] in {line for line, *_ in host}
               for n, _ in red.idle_gaps)


# ------------------------------------------------------- per chip
PER_CHIP = ("linear_share_pct", "linear_roofline", "device_idle_pct",
            "decode_step_ms")


def reader(name):
    return _load_module(HERE / "metrics" / f"{name}.py", f"reader_{name}")


def readings(red):
    obs = SimpleNamespace(trace=red)
    return {m: reader(m).read(obs) for m in PER_CHIP}


def test_four_planes_read_as_one():
    """The same operations on four device planes, as a (1, 4) mesh runs
    them: every per-layer reading and every second of the breakdown is
    one chip's, as on one plane."""
    ops = [op(WHILE, 0, 4000), op(LINEAR, 0, 1000), op(FUSION, 1000, 500),
           op(LINEAR, 3000, 1000), op(FUSION, 9000, 700),
           op(LINEAR, 12000, 3000)]
    mods = [op("jit_paged_decode_step(7)", 0, 4000),
            op("jit_paged_decode_step(7)", 12000, 3000)]
    host = [("python", "$array.py:631 _value", 4100, 4800),
            ("python", "engine.iter", 9800, 2100)]
    classes = trace.load_classes(KERNELS)

    def reduce(n):
        planes = [(f"/device:TPU:{i}", ops, mods) for i in range(n)]
        return trace.reduce_ops(planes, host, 2e-5, classes, PEAK)

    one, four = reduce(1), reduce(4)
    assert readings(four) == pytest.approx(readings(one), rel=1e-12)
    assert 0 < readings(four)["linear_share_pct"] <= 100
    b1, b4 = one.breakdown(), four.breakdown()
    for part in ("device_ops", "idle_gaps"):
        assert [n for n, _ in b4[part]] == [n for n, _ in b1[part]]
        assert [s for _, s in b4[part]] == pytest.approx(
            [s for _, s in b1[part]], rel=1e-12)
    assert b1["idle_gaps"] == [["python: $array.py:631 _value",
                                pytest.approx(5e-6)],
                               ["python: engine.iter",
                                pytest.approx(2.3e-6)]]


def test_mfu_is_per_chip():
    """The same finished requests in the same window read a quarter on
    four chips of what they read on one."""
    cell = load_cell(ROOT, "internlm2-1_8b-2of4.chat_short")
    red = trace.reduce_ops([("/device:TPU:0", [op(FUSION, 0, 10)], [])],
                           [], 2.5, [], PEAK)
    report = SimpleNamespace(stats=[
        SimpleNamespace(prompt_len=p, new_tokens=n)
        for p, n in ((64, 200), (80, 512), (16, 33))])

    def mfu(chips):
        obs = Observed(cell=dataclasses.replace(cell, chips=chips), slots=16,
                       setup_s=1.0, window_s=2.5, segments=[], done=[],
                       device_kind="TPU v5 lite", trace=red,
                       traced_report=report)
        return reader("mfu").read(obs)

    assert 0 < mfu(1) <= 100
    assert mfu(4) == mfu(1) / 4


@pytest.mark.parametrize("name", ["decode_2of4", "engine_spans_2of4"])
def test_recorded_traces_read_as_before(name):
    """The recorded one-device traces read exactly what the reduction
    read before it reported per chip
    (``fixtures/one_device_readings.json``)."""
    want = json.loads((FIXTURE / "one_device_readings.json").read_text())
    devices, host = trace.load_ops(FIXTURE / f"{name}.xplane.pb")
    ops = devices[0][1]
    first = min(o.start_ns for o in ops)
    last = max(o.start_ns + o.dur_ns for o in ops)
    red = trace.reduce_ops(devices, host, (last - first) * 1e-9,
                           trace.load_classes(KERNELS), PEAK)
    got = dict(readings(red), busy_s=red.busy_s, breakdown=red.breakdown())
    assert json.loads(json.dumps(got)) == want[name]
