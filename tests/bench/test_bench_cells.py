"""The harness on the CPU: a cell built from added files alone, and the
comparison that decides ``correct`` catching the control and planted
faults.  These runs skip run.py's look for a chip and drive the rest of
a run with the program's jnp tier."""

import json
import shutil
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import arrivals
from bench.harness import BenchError, load_cell, run_cell

ROOT = Path(__file__).resolve().parents[2]


def add_cell(root: Path, name: str, widths: dict, engine: dict,
             prompts, outputs, limits: dict, n: int = 12,
             check: int = 4, shares=None) -> str:
    """Write a configuration, a traffic mix, a limit and a BENCHMARK.json
    naming them under ``root``; returns the cell's name."""
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "internlm2-1_8b-2of4.json").read_text())
    cfg.update(name=f"{name}-cfg", **widths)
    (root / "bench" / "configs" / f"{name}-cfg.json").write_text(
        json.dumps(cfg))
    mix = {"name": f"{name}_mix", "engine": engine,
           "prompt_tokens": {"values": prompts,
                             "p": shares or [1.0] * len(prompts)},
           "output_tokens": outputs, "load": 0.85, "segment_requests": n,
           "check_requests": check}
    mix["rate_per_iteration"] = arrivals.capacity_rate(mix)
    (root / "bench" / "traffic" / f"{name}_mix.json").write_text(
        json.dumps(mix))
    cell = f"{name}-cfg.{name}_mix"
    (root / "bench" / "limits" / f"{cell}.json").write_text(
        json.dumps({k: {"limit": v} for k, v in limits.items()}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(bench["configs"][0], name=f"{name}-cfg",
                             file=f"bench/configs/{name}-cfg.json")]
    bench["workloads"] = [dict(bench["workloads"][0], name=cell,
                               config=f"{name}-cfg",
                               traffic=f"{name}_mix")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=256)
TINY_ENGINE = {"slots": 4, "max_len": 64, "block_len": 8,
               "prefill_chunk": 16}
# the sound program reads widest gaps under 0.03 at this size; a token
# served in place of the reference's choice reads whole logits
TINY_LIMITS = {"widest_gap": 0.25, "mean_gap": 0.01}
# medium, one segment (CPU, jnp tier), seeds 11-16: sound runs read
# widest gaps 0.0209 / 0 / 0.0133 / 0.0167 / 0.0267 / 0.0130 and mean
# gaps 2.40e-4 / 0 / 1.11e-4 / 1.97e-4 / 2.47e-4 / 2.19e-4; the int8
# control (weight-only on the jnp tier) 0.0351 / 0.0541 / 0.0322 /
# 0.0309 / 0.0374 / 0.0217 and 9.5e-4 / 1.14e-3 / 8.2e-4 / 3.4e-4 /
# 9.0e-4 / 3.6e-4.  At this size the two overlap on some seeds (16's
# control against 15's sound run), so the test holds seeds 11-14, on
# which they stand apart; the chip's cells are judged at full size,
# with limits set from a dozen seeds.
MEDIUM = dict(hidden_size=512, intermediate_size=1536,
              num_attention_heads=8, num_key_value_heads=4,
              num_hidden_layers=8, vocab_size=8192)
MEDIUM_LIMITS = {"widest_gap": 0.025, "mean_gap": 5e-4}


@pytest.fixture
def tiny_cell(tmp_path):
    name = add_cell(tmp_path, "throwaway", TINY, TINY_ENGINE, [8, 16, 24],
                    {"median": 8, "sigma": 0.7, "min": 2, "max": 24},
                    TINY_LIMITS)
    return load_cell(tmp_path, name)


def run(cell, seed=3, seconds=0.0, **kw):
    """One run; ``seconds=0`` measures exactly one segment, so the
    sample compared is a function of the seed alone."""
    return run_cell(cell, seed, seconds, False, t0=time.perf_counter(),
                    backend="jnp", log=lambda *_: None, **kw)


def test_cell_from_added_files_alone(tiny_cell):
    cell = tiny_cell
    assert cell.config["hidden_size"] == 64
    assert cell.mix["engine"] == TINY_ENGINE
    assert cell.limits["widest_gap"]["limit"] == TINY_LIMITS["widest_gap"]
    res = run(cell, seconds=0.5)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] % cell.mix["segment_requests"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "latency_p95_s",
                                   "setup_s"}
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    assert {k: res["checks"][k]["limit"] for k in TINY_LIMITS} == TINY_LIMITS


def test_missing_file_is_refused(tmp_path):
    name = add_cell(tmp_path, "gone", TINY, TINY_ENGINE, [8],
                    {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
                    {"mean_gap": 1.0})
    shutil.rmtree(tmp_path / "bench" / "traffic")
    with pytest.raises(BenchError, match="no such file"):
        load_cell(tmp_path, name)
    with pytest.raises(BenchError, match="no workload"):
        load_cell(tmp_path, "nope.nope")


def test_every_benchmark_cell_loads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = load_cell(ROOT, w["name"])
        assert cell.limits and all(v["limit"] > 0
                                   for v in cell.limits.values())
        assert arrivals.longest(arrivals.segment(
            cell.mix, 1, 0, cell.config["vocab_size"])) <= \
            cell.mix["engine"]["max_len"]


def _wrap_decode(monkeypatch, fault):
    import repro.models.paged as paged

    real = paged.paged_decode_step

    def broken(params, caches, *args, **kw):
        logits, new = real(params, caches, *args, **kw)
        return fault(logits, caches, new)

    monkeypatch.setattr(paged, "paged_decode_step", broken)


def test_fault_altered_token_is_not_correct(tiny_cell, monkeypatch):
    # every decode step serves the token next to its best one
    _wrap_decode(monkeypatch,
                 lambda logits, old, new: (jnp.roll(logits, 1, -1), new))
    res = run(tiny_cell)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > TINY_LIMITS["widest_gap"]


def test_fault_state_left_unchanged_is_not_correct(tiny_cell, monkeypatch):
    # the decode step hands back the KV pools it was given
    _wrap_decode(monkeypatch, lambda logits, old, new: (logits, old))
    res = run(tiny_cell)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > TINY_LIMITS["widest_gap"]


def test_fault_half_the_batch_left_out_is_not_correct(tiny_cell,
                                                    monkeypatch):
    # the decode step computes the first half of the slots and hands
    # their logits to the other half too
    def halve(logits, old, new):
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]])[:logits.shape[0]], new

    _wrap_decode(monkeypatch, halve)
    res = run(tiny_cell)
    assert not res["correct"]
    assert res["checks"]["widest_gap"]["value"] > TINY_LIMITS["widest_gap"]


@pytest.fixture
def medium_cell(tmp_path):
    name = add_cell(tmp_path, "medium", MEDIUM, TINY_ENGINE, [8, 16, 24],
                    {"median": 16, "sigma": 0.5, "min": 4, "max": 32},
                    MEDIUM_LIMITS, check=8, shares=[0.3, 0.4, 0.3])
    return load_cell(tmp_path, name)


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_int8_control_is_not_correct(medium_cell, seed):
    sound = run(medium_cell, seed=seed)
    assert sound["correct"], sound["checks"]
    control = run(medium_cell, seed=seed, control="int8")
    assert not control["correct"], control["checks"]
