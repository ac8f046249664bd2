"""The Mistral family's adapter and reference at a tiny size: served on
one CPU device and over a (1, 4) mesh of four, the run is ``correct``;
the int8 control and a decode step that hands back the pool it was given
are not.  The mesh run is a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import load_cell
from test_bench_cells import (MEDIUM, MEDIUM_LIMITS, TINY_ENGINE,
                              TINY_LIMITS, _wrap_decode, add_cell, run)
from test_bench_mesh import TINY_TP

ROOT = Path(__file__).resolve().parents[2]


def mistral_cell(root: Path, widths: dict, limits: dict, mesh=None,
                 **kw) -> str:
    """A tiny cell of the mistral family (its adapter, its reference and
    the program's mistral preset), over ``mesh`` where given."""
    name = add_cell(root, "mistral", widths, TINY_ENGINE, [8, 16, 24],
                    {"median": 16, "sigma": 0.5, "min": 4, "max": 32},
                    limits, **kw)
    path = root / "bench" / "configs" / "mistral-cfg.json"
    cfg = json.loads(path.read_text())
    cfg.update(family="mistral", rms_norm_eps=1e-5, rope_theta=1e6)
    cfg["program"]["arch"] = "mistral_large_123b"
    if mesh is not None:
        cfg["program"]["mesh"] = mesh
        bench = json.loads((root / "BENCHMARK.json").read_text())
        bench["workloads"][0]["chips"] = mesh[0] * mesh[1]
        (root / "BENCHMARK.json").write_text(json.dumps(bench))
    path.write_text(json.dumps(cfg))
    return name


def test_adapter_states_the_published_epsilon(tmp_path):
    cell = load_cell(tmp_path, mistral_cell(tmp_path, TINY_TP, TINY_LIMITS))
    mc = cell.adapter().model_config(cell.config)
    assert mc.rms_norm_eps == 1e-5 and mc.rope_theta == 1e6
    assert (mc.num_heads, mc.num_kv_heads) == (8, 4)


@pytest.fixture
def one_device(tmp_path):
    return load_cell(tmp_path, mistral_cell(tmp_path, TINY_TP, TINY_LIMITS))


def test_sound_run_is_correct(one_device):
    res = run(one_device)
    assert res["correct"], res["checks"]
    assert res["checks"]["mean_gap"]["value"] < TINY_LIMITS["mean_gap"]


def test_pool_left_unchanged_is_not_correct(one_device, monkeypatch):
    _wrap_decode(monkeypatch, lambda logits, old, new: (logits, old))
    res = run(one_device)
    assert not res["correct"]
    assert res["checks"]["mean_gap"]["value"] > TINY_LIMITS["mean_gap"]


def test_int8_control_is_not_correct(tmp_path):
    # the medium widths of test_bench_cells, at which the int8 control
    # stands apart from the sound run on seed 11
    cell = load_cell(tmp_path, mistral_cell(
        tmp_path, MEDIUM, MEDIUM_LIMITS, check=8, shares=[0.3, 0.4, 0.3]))
    sound = run(cell, seed=11)
    assert sound["correct"], sound["checks"]
    control = run(cell, seed=11, control="int8")
    assert not control["correct"], control["checks"]


CHILD = r"""
import json, sys, time
from pathlib import Path

from bench.harness import load_cell, run_cell

cell = load_cell(Path(sys.argv[1]), sys.argv[2])
res = run_cell(cell, 3, 0.0, False, t0=time.perf_counter(), backend="jnp",
               log=lambda *_: None)
print(json.dumps(res))
"""


def test_sound_run_over_four_devices_is_correct(tmp_path):
    name = mistral_cell(tmp_path, TINY_TP, TINY_LIMITS, mesh=[1, 4])
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    r = subprocess.run([sys.executable, "-c", CHILD, str(tmp_path), name],
                       capture_output=True, text=True, env=env, timeout=600,
                       cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-4000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"], res["checks"]
    assert res["checks"]["mean_gap"]["value"] < TINY_LIMITS["mean_gap"]
    assert res["compiles_in_window"] == 0

