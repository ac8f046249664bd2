"""A cell whose configuration states a mesh (``program.mesh``), on four
CPU devices: ``load_cell`` holds the mesh to the cell's chips, the
weights are built already placed as ``prepare`` places them, and the run
is correct.  The run is a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the test process
keeps the one CPU device the other tests see); it skips run.py's look
for a chip and serves on the program's jnp tier."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.harness import BenchError, load_cell
from test_bench_cells import TINY, TINY_ENGINE, TINY_LIMITS, add_cell

ROOT = Path(__file__).resolve().parents[2]
# 4 KV heads, so that whole heads divide the model axis, as Mistral-Large's
# 8 do over 4 chips: every linear is then split over the mesh
TINY_TP = dict(TINY, num_attention_heads=8, num_key_value_heads=4)


def mesh_cell(root: Path, mesh, chips: int) -> str:
    """The tiny cell under ``root``, its configuration stating ``mesh``
    (none where ``None``) and the cell asking for ``chips``."""
    name = add_cell(root, "tp", TINY_TP, TINY_ENGINE, [8, 16, 24],
                    {"median": 8, "sigma": 0.7, "min": 2, "max": 24},
                    TINY_LIMITS)
    path = root / "bench" / "configs" / "tp-cfg.json"
    cfg = json.loads(path.read_text())
    if mesh is not None:
        cfg["program"]["mesh"] = mesh
    path.write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"][0]["chips"] = chips
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


@pytest.mark.parametrize("mesh,chips,refusal", [
    ([1, 2], 4, "spans 2 chips, the cell asks for 4"),
    ([2, 4], 4, "spans 8 chips"),
    (None, 4, "states no program.mesh"),
    ([4], 4, r"is not \[data, model\]"),
])
def test_mesh_must_span_the_cells_chips(tmp_path, mesh, chips, refusal):
    name = mesh_cell(tmp_path, mesh, chips)
    with pytest.raises(BenchError, match=refusal) as e:
        load_cell(tmp_path, name)
    assert name in str(e.value)


def test_mesh_that_spans_the_cells_chips_loads(tmp_path):
    cell = load_cell(tmp_path, mesh_cell(tmp_path, [1, 4], 4))
    assert cell.chips == 4
    assert cell.config["program"]["mesh"] == [1, 4]


CHILD = r"""
import json, sys, time
from pathlib import Path

import jax
import numpy as np

from bench import sut
from bench.harness import load_cell, run_cell

root, name = Path(sys.argv[1]), sys.argv[2]
cell = load_cell(root, name)
ref, adapter = cell.reference(), cell.adapter()
make, kept = adapter.program_params, []


def keep(*args, **kw):
    kept.append(make(*args, **kw))
    return kept[-1]


adapter.program_params = keep
served = sut.Served(ref, adapter, 3, cell.config, cell.mix, backend="jnp")
adapter.program_params = make
built = kept.pop()
served_leaves = jax.tree.leaves(served.prepared.params)
moved = sum(a is not b for a, b in zip(jax.tree.leaves(built), served_leaves))
linear = {}
for path, leaf in jax.tree_util.tree_leaves_with_path(built):
    keys = [getattr(k, "key", None) for k in path]
    if "mixer" in keys or "ffn" in keys:
        linear["/".join(str(k) for k in keys[-2:])] = {
            "devices": len(leaf.sharding.device_set),
            "share": leaf.addressable_shards[0].data.size / leaf.size}
one = adapter.program_params(ref, 3, cell.config)
same = all(np.array_equal(np.asarray(a), np.asarray(b))
           for a, b in zip(jax.tree.leaves(built), jax.tree.leaves(one)))
mesh = served.spec.mesh
del served, served_leaves, built, one
res = run_cell(cell, 3, 0.0, False, t0=time.perf_counter(), backend="jnp",
               log=lambda *_: None)
print(json.dumps({"mesh": mesh, "linear": linear, "moved": moved,
                  "same_values": same, "result": res}))
"""


def test_mesh_cell_is_correct_over_four_devices(tmp_path):
    name = mesh_cell(tmp_path, [1, 4], 4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    r = subprocess.run([sys.executable, "-c", CHILD, str(tmp_path), name],
                       capture_output=True, text=True, env=env, timeout=600,
                       cwd=tmp_path)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["mesh"] == [1, 4]
    # built already placed: every linear's leaves lie on the four
    # devices, its weight values split over them (the positions of a
    # row-parallel linear are replicated by the program's rules), and
    # prepare moves none of them
    assert out["moved"] == 0
    assert len(out["linear"]) == 14
    assert all(v["devices"] == 4 for v in out["linear"].values())
    assert all(v["share"] == 0.25 for k, v in out["linear"].items()
               if k.endswith("/values"))
    # placed as it is built, the tree holds the one-device build's values
    assert out["same_values"]
    res = out["result"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["device"]["count"] == 4
    # Not asserted: no compilation in the window.  The program makes its
    # KV pool without a placement (repro.serving.engine.init_paged_caches)
    # and its first step reshards it, so the paged steps are traced again
    # inside the window; the fix belongs to the program, not to the
    # benchmark, and no extra warm-up here hides it.
