"""``chip_smoke.py`` contract on the CPU: it refuses to report a result
without a TPU, and its phases rehearse end to end at smoke widths.

Each run is a child process with ``JAX_PLATFORMS=cpu`` (it never loads
the TPU library) and its compilation cache in the test's tmp dir.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run(tmp_path, *args, devices=1):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("REPRO_KERNEL_BACKEND", None)
    return subprocess.run([sys.executable, str(ROOT / "chip_smoke.py"),
                           *args], capture_output=True, text=True, env=env,
                          timeout=600)


def _has_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok") is not None:
                return True
        except (ValueError, AttributeError):
            continue
    return False


def test_without_a_tpu_fails_and_prints_no_result(tmp_path):
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not _has_result_line(r.stdout)


def test_smoke_rehearsal_serves_both_layouts(tmp_path):
    r = _run(tmp_path, "--smoke")
    assert r.returncode == 0, r.stderr[-4000:]
    out = r.stdout
    assert "dense 4:4: 8/8 requests completed with 32 tokens" in out
    assert "compressed 2:4: 8/8 requests completed with 32 tokens" in out
    assert out.count("0 off the interpret kernel tier") == 2
    assert "parity 2:4 kernel vs jnp" in out
    assert not _has_result_line(out)


def test_smoke_rehearsal_tensor_parallel(tmp_path):
    r = _run(tmp_path, "--smoke", "--chips", "4", devices=4)
    assert r.returncode == 0, r.stderr[-4000:]
    assert ("report tensor-parallel float32 2:4 vs one chip: 8/8 requests "
            "with identical greedy tokens") in r.stdout
    assert not _has_result_line(r.stdout)
