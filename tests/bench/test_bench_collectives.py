"""``collective_share_pct`` on reductions made by hand: collective
operations (a start/done pair, a plain ``all-reduce``, a ``psum`` as a
TPU trace names one) count, a ``fusion`` does not, and a trace with no
collective reads ``None``."""

from types import SimpleNamespace

import pytest

from bench import trace
from bench.harness import HERE, _load_module

READER = _load_module(HERE / "metrics" / "collective_share_pct.py",
                      "bench_metric_collective_share_pct")
# ``%all-gather-start.3 = ...`` and the rest, as the reduction names kinds
TEXT = {"all-gather-start": "%all-gather-start.3 = (bf16[4,8]) "
                            "all-gather-start(bf16[1,8] %p)",
        "all-gather-done": "%all-gather-done.3 = bf16[4,8] "
                           "all-gather-done((bf16[4,8]) %all-gather-start.3)",
        "all-reduce": "%all-reduce.7 = f32[16,64] all-reduce(f32[16,64] %x)",
        "psum": "%psum.2 = f32[16,64] all-reduce(f32[16,64] %y)",
        "fusion": "%fusion.1 = bf16[16,64] fusion(bf16[16,64] %z), "
                  "kind=kLoop"}


def reduced(seconds: dict, busy_s: float = 10.0):
    top = sorted(seconds.items(), key=lambda kv: -kv[1])
    return trace.Reduced(window_s=12.0, busy_s=busy_s, programs={},
                         linear_calls=0, linear_s=0.0, linear_least_s=None,
                         top_ops=top, idle_gaps=[])


def read(red):
    return READER.read(SimpleNamespace(trace=red))


def test_kinds_are_named_as_the_reduction_names_them():
    for kind, text in TEXT.items():
        assert trace.Op(text, 0, 1).kind == kind


@pytest.mark.parametrize("counted,seconds", [
    ({"all-gather-start", "all-gather-done"}, 0.25 + 0.5),
    ({"all-reduce"}, 1.0),
    ({"psum"}, 2.0),
])
def test_collectives_count_and_a_fusion_does_not(counted, seconds):
    times = {"all-gather-start": 0.25, "all-gather-done": 0.5,
             "all-reduce": 1.0, "psum": 2.0, "fusion": 4.0}
    red = reduced({k: v for k, v in times.items()
                   if k in counted or k == "fusion"})
    assert read(red) == pytest.approx(100.0 * seconds / 10.0)


def test_every_collective_at_once():
    red = reduced({"all-gather-start": 0.25, "all-gather-done": 0.5,
                   "all-reduce": 1.0, "fusion": 4.0, "linear": 3.0})
    assert read(red) == pytest.approx(17.5)


def test_no_collective_reads_none():
    assert read(reduced({"fusion": 4.0, "linear": 3.0})) is None
    assert read(None) is None
