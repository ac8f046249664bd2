"""Block-size autotuner for the dispatch engine.

Per-(kernel, problem, backend) best block sizes, resolved in three layers:

1. an in-process cache (dict) — hot path, no I/O;
2. a JSON store under ``experiments/autotune/`` (one file per backend) so
   tuned blocks survive process restarts and can feed BENCH trajectories;
3. live timing of the kernel over its legal block candidates (``tune``),
   which then populates both layers.

Keys are deterministic strings (shape/sparsity/dtype), so a tuned entry on
one host applies to any run of the same problem on the same backend.

Stores are additionally keyed by the **device kind** actually executing
(``jax.devices()[0].device_kind``: ``cpu-interpret.json`` under CPU
emulation, ``tpu-v5-lite.json`` on a v5e, ``tpu-v6-lite.json`` on a
v6e): block sizes timed under interpret-mode emulation say nothing about
Mosaic behavior, and blocks tuned on one chip generation say little
about another, so neither is ever served to the wrong run.
"""

from __future__ import annotations

import json
import logging
import os
import re
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax

__all__ = [
    "cache_key",
    "lookup",
    "record",
    "tune",
    "clear_memory_cache",
    "store_path",
    "device_kind",
    "stats",
    "reset_stats",
]

Blocks = Tuple[int, int, int]

_ENV_DIR = "REPRO_AUTOTUNE_DIR"
# anchored to the checkout (<checkout>/src/repro/kernels/autotune.py),
# not to whatever directory the process was started from
_DEFAULT_DIR = str(Path(__file__).resolve().parents[3]
                   / "experiments" / "autotune")

_log = logging.getLogger(__name__)

# (store name) -> {key: [bb, bke, bo]}; None = not yet loaded from disk
_MEM: Dict[str, Optional[Dict[str, list]]] = {}

# lookup outcomes since process start / last reset (dispatch-plan report)
_STATS = {"hits": 0, "misses": 0}


def cache_key(kernel: str, b: int, ke: int, o: int, n: int, m: int, dtype,
              epilogue: Optional[str] = None,
              activation: Optional[str] = None) -> str:
    """Deterministic per-problem key; dtype is a first-class axis (an int8
    problem and its fp32 twin must never share tuned blocks).  A fused
    epilogue lattice point (``"bias+silu"``, ``"silu_mul+requant:int8"``,
    ...) is likewise a key axis: the flush cost changes the optimal
    blocks, so fused and bare plans never share tuned entries.  An
    in-kernel activation-sparsity skip (``"top64"``, ``"thr0.5"``,
    ``"zeros"``) changes the per-block work the same way, so it gets its
    own tail too."""
    from repro.kernels.registry import dtype_name

    tail = f"_epi[{epilogue}]" if epilogue else ""
    if activation:
        tail += f"_act[{activation}]"
    return f"{kernel}/b{b}_ke{ke}_o{o}_n{n}m{m}_{dtype_name(dtype)}{tail}"


def device_kind() -> str:
    """Chip actually executing, as JAX names it ("cpu", "TPU v5 lite",
    ...).  A backend that fails to start raises here — it is never
    read as the CPU."""
    return str(jax.devices()[0].device_kind)


def _store_name(backend: str) -> str:
    # "TPU v5 lite" -> "tpu-v5-lite"; the backend suffixes the name only
    # when it is not the device's own platform (interpret on CPU/TPU)
    kind = re.sub(r"[^a-z0-9]+", "-", device_kind().lower()).strip("-")
    platform = jax.devices()[0].platform
    return kind if backend == platform else f"{kind}-{backend}"


def store_path(backend: str) -> str:
    base = os.environ.get(_ENV_DIR, _DEFAULT_DIR)
    return os.path.join(base, f"{_store_name(backend)}.json")


def _load(backend: str) -> Dict[str, list]:
    name = _store_name(backend)
    cached = _MEM.get(name)
    if cached is not None:
        return cached
    path = store_path(backend)
    table: Dict[str, list] = {}
    try:
        with open(path) as f:
            raw = json.load(f)
        if isinstance(raw, dict):
            table = {
                k: v for k, v in raw.items()
                if isinstance(v, list) and len(v) == 3
            }
    except (OSError, ValueError):
        pass  # missing or corrupt store — start fresh
    _MEM[name] = table
    return table


def _save(backend: str) -> None:
    table = _MEM.get(_store_name(backend)) or {}
    path = store_path(backend)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    # atomic replace so a crashed run can't corrupt the store
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(table, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def lookup(backend: str, key: str) -> Optional[Blocks]:
    hit = _load(backend).get(key)
    _STATS["hits" if hit else "misses"] += 1
    return tuple(hit) if hit else None


def record(backend: str, key: str, blocks: Blocks, persist: bool = True) -> None:
    _load(backend)[key] = list(blocks)
    if persist:
        _save(backend)


def tune(
    runner: Callable[[Blocks], jax.Array],
    candidates: Sequence[Blocks],
    *,
    backend: str,
    key: str,
    iters: int = 3,
    persist: bool = True,
) -> Optional[Blocks]:
    """Time ``runner`` over each legal candidate; cache and return the best.

    ``runner(blocks)`` must execute the kernel end-to-end (it is called
    once for warm-up/compile, then ``iters`` times under the clock).
    Returns ``None`` — and records nothing — when every candidate failed,
    so a broken kernel/problem pair never poisons the cache and the
    caller can fall back.
    """
    hit = lookup(backend, key)
    if hit is not None:
        return hit
    assert candidates, "tune() requires at least one legal candidate"
    best, best_t = None, float("inf")
    for blocks in candidates:
        try:
            jax.block_until_ready(runner(blocks))  # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(runner(blocks))
            dt = (time.perf_counter() - t0) / iters
        except Exception as e:  # candidate failed to compile/run
            _log.warning("autotune %s: candidate blocks %s dropped: %s: %s",
                         key, tuple(blocks), type(e).__name__, e)
            continue
        if dt < best_t:
            best, best_t = blocks, dt
    if best is None:
        return None
    record(backend, key, best, persist=persist)
    return tuple(best)


def stats() -> Dict[str, int]:
    """Cache-lookup outcomes since start/reset (for the dispatch report)."""
    return dict(_STATS)


def reset_stats() -> None:
    _STATS["hits"] = _STATS["misses"] = 0


def clear_memory_cache() -> None:
    """Drop the in-process layer (tests; the JSON store is untouched)."""
    _MEM.clear()
