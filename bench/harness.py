"""One run of one cell, found by name in ``BENCHMARK.json``.

Everything a cell is made of is a file found by its name: the
configuration (``BENCHMARK.json`` gives its path), the traffic mix
(``bench/traffic/<traffic>.json``), the correctness limits
(``bench/limits/<workload>.json``), the configuration's family's reference
(``bench/reference/<family>.py``) and its adapter to the program's
parameter tree (``bench/adapters/<family>.py``), and one reader per metric
(``bench/metrics/<metric>.py``, a ``read(observed)`` that returns a
number, or ``None`` where it finds nothing to read).

A run: set-up (weights from the seed, ``prepare``, one warm-up request
per prefill shape), then a window of whole segments of traffic through
``Engine.run`` until ``seconds`` have passed, then the comparison with
the reference.  With ``trace`` the first segment runs under the
profiler and the per-layer metrics are read from it.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional


from . import arrivals, correctness, counting
from .trace import reduce_trace

HERE = Path(__file__).resolve().parent
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class BenchError(RuntimeError):
    """The cell cannot be run as its files describe it."""


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def sparsity(self):
        sp = self.config["program"].get("sparsity")
        return None if sp is None else tuple(sp)

    def reference(self):
        name = self.config["family"]
        return _load_module(HERE / "reference" / f"{name}.py",
                            f"bench_reference_{name}")

    def adapter(self):
        name = self.config["family"]
        return _load_module(HERE / "adapters" / f"{name}.py",
                            f"bench_adapter_{name}")


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    return json.loads(path.read_text())


def _check_mesh(workload: str, chips: int, config: dict) -> None:
    """A cell on more than one chip runs over its configuration's mesh
    (``program.mesh``, ``[data, model]``), which spans exactly its chips."""
    mesh = config["program"].get("mesh")
    if mesh is None:
        if chips > 1:
            raise BenchError(f"{workload}: {chips} chips, and its "
                             f"configuration states no program.mesh")
        return
    if (len(mesh) != 2 or not all(isinstance(a, int) and a > 0
                                  for a in mesh)):
        raise BenchError(f"{workload}: program.mesh {mesh!r} is not "
                         f"[data, model] of positive whole numbers")
    if mesh[0] * mesh[1] != chips:
        raise BenchError(f"{workload}: program.mesh {mesh} spans "
                         f"{mesh[0] * mesh[1]} chips, the cell asks for "
                         f"{chips}")


def load_cell(root: Path, workload: str) -> Cell:
    """The cell ``workload`` as the files under ``root`` describe it."""
    bench = _read_json(root / "BENCHMARK.json")
    w = _by_name(bench["workloads"], workload, "workload")
    c = _by_name(bench["configs"], w["config"], "config")
    chips, config = int(w["chips"]), _read_json(root / c["file"])
    _check_mesh(workload, chips, config)

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(name=workload, chips=chips, config=config,
                mix=_read_json(root / "bench" / "traffic"
                               / f"{w['traffic']}.json"),
                limits=_read_json(root / "bench" / "limits"
                                  / f"{workload}.json"),
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


@dataclasses.dataclass
class Observed:
    """What a run saw: the readers' one argument."""

    cell: Cell
    slots: int
    setup_s: float
    window_s: float
    segments: List[Any]            # ServingReport per segment
    done: List[tuple]              # (drawn request, RequestStats)
    device_kind: str
    trace: Any = None              # trace.Reduced of the traced segment
    traced_report: Any = None      # ServingReport of the traced segment

    def peak(self) -> dict:
        """One chip's published peaks; an unknown kind fails the run."""
        return counting.peaks(self.device_kind)


def read_metrics(obs: Observed, entries: List[dict]) -> Dict[str, dict]:
    out = {}
    for m in entries:
        reader = _load_module(HERE / "metrics" / f"{m['name']}.py",
                              f"bench_metric_{m['name']}")
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class _CompileCounter:
    def __init__(self):
        import jax.monitoring as mon

        self.count = 0
        self.armed = False
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._on)


def use_compile_cache(root: Path) -> Path:
    """JAX's persistent compilation cache in ``<root>/.jax_cache``: one
    fixed directory inside the checkout (the path is part of the cache's
    key); every program is kept, however quick its compile, and nothing
    is evicted."""
    import jax

    cache = root / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peaks() -> List[int]:
    """``peak_bytes_in_use`` of each device, in ``jax.devices()`` order."""
    import jax

    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, control: Optional[str] = None,
             backend: str = "auto", log=print) -> dict:
    """Set up, measure, compare; returns the result line's object."""
    import jax

    from .sut import Served

    ref = cell.reference()
    mix = cell.mix
    vocab = cell.config["vocab_size"]
    t_init = time.perf_counter()
    peak = counting.peaks(device_info()["kind"]) if trace else None
    served = Served(ref, cell.adapter(), seed, cell.config, mix,
                    control=control, backend=backend)
    t_made = time.perf_counter()
    served.run(arrivals.warmup_requests(mix))
    log(f"set-up: {served.weight_bytes()} weight bytes, "
        f"{served.engine.kv_bytes()} KV pool bytes; JAX start "
        f"{t_init - t0:.2f}s, weights and prepare {t_made - t_init:.2f}s, "
        f"warm-up {time.perf_counter() - t_made:.2f}s; {served.spec}")
    counter = _CompileCounter()
    segments, done, walls = [], [], []
    traced = traced_report = None
    tdir = tempfile.TemporaryDirectory() if trace else None
    window_start = time.perf_counter()
    setup_s = window_start - t0
    counter.armed = True
    try:
        while True:
            drawn = arrivals.segment(mix, seed, len(segments), vocab)
            t_seg = time.perf_counter()
            if tdir is not None and not segments:
                jax.profiler.start_trace(tdir.name)
                report = served.run(drawn)
                traced_s = time.perf_counter() - t_seg
                jax.profiler.stop_trace()
                traced_report = report
            else:
                report = served.run(drawn)
            walls.append(time.perf_counter() - t_seg)
            segments.append(report)
            by_rid = {st.rid: st for st in report.stats}
            done += [(r, by_rid[r.rid]) for r in drawn if r.rid in by_rid]
            if time.perf_counter() - window_start >= seconds:
                break
        window_s = time.perf_counter() - window_start
    finally:
        counter.armed = False
        counter.close()
    if tdir is not None:
        with tdir:
            traced = reduce_trace(Path(tdir.name), traced_s,
                                  HERE / "kernels", peak)
    dev = device_info()
    peaks = memory_peaks()
    dev["memory_peak_bytes"] = max(peaks)
    attempted = len(segments) * mix["segment_requests"]
    log(f"window: {len(segments)} segment(s) of {mix['segment_requests']} "
        f"requests, {len(done)}/{attempted} finished, "
        f"{sum(r.generated_tokens for r in segments)} tokens in "
        f"{window_s:.3f}s (segments {[round(w, 2) for w in walls]} s); "
        f"compilations in the window: {counter.count}; peak bytes per "
        f"device {peaks}")
    obs = Observed(cell=cell, slots=served.spec.slots, setup_s=setup_s,
                   window_s=window_s, segments=segments, done=done,
                   device_kind=dev["kind"], trace=traced,
                   traced_report=traced_report)
    metrics = read_metrics(obs, cell.per_layer if trace else cell.end_to_end)

    del served
    gc.collect()
    picked = correctness.sample(seed, done, mix["check_requests"])
    t_ref = time.perf_counter()
    numbers, per_request = correctness.compare(
        ref, seed, cell.config, cell.sparsity, picked,
        mix["engine"]["max_len"], mix["output_tokens"]["max"])
    log(f"reference: {len(picked)} requests, "
        f"{sum(len(st.tokens) for _, st in picked)} served tokens, "
        f"{numbers}, widest per request "
        f"{[round(g, 6) for g in per_request]}, "
        f"{time.perf_counter() - t_ref:.1f}s")
    short = sum(1 for r, st in done if len(st.tokens) != r.max_new_tokens)
    failed = attempted - len(done) + short
    checks = {name: {"value": numbers[name], "limit": float(spec["limit"])}
              for name, spec in cell.limits.items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": ok,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if traced is not None:
        dev["busy_s"] = traced.busy_s
        dev["window_s"] = traced.window_s
        result["breakdown"] = traced.breakdown()
    result["compiles_in_window"] = counter.count
    result["checks"] = checks
    return result


def print_result(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The check lines last on standard error, the result last on
    standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=err, flush=True)
    print(json.dumps(result), file=out, flush=True)
