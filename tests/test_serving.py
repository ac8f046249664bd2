"""The serving subsystem: paged KV parity, scheduler policies, the
continuous-batching engine, and the prep-API deprecation shims.

The acceptance contracts pinned here:

- paged-KV decode is **numerically identical** to contiguous-cache
  decode per request (fp32 exact; int8-quantized weights exact too —
  both paths contract the same quantized operands, and masked paged
  positions hit ``-inf`` before the softmax so they contribute exactly
  zero);
- the engine completes a seeded 16-request Poisson trace with strictly
  higher completed-requests-per-model-call than the lockstep loop at
  equal batch width;
- ragged retirement, block reuse after eviction, eviction-transparent
  outputs, and interleaving determinism under a fixed seed;
- ``repro.serving.prepare`` subsumes the old offline-prep entry points,
  which keep working behind warn-once ``DeprecationWarning`` shims.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import serving  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.models import (decode_step, init_caches,  # noqa: E402
                          init_params, paged_decode_step,
                          paged_prefill_chunk)
from repro.models.paged import init_paged_caches  # noqa: E402
from repro.serving.scheduler import PagedScheduler, Request  # noqa: E402

ARCH = "internlm2_1_8b"


def _spec(**kw):
    base = dict(layout="dense", slots=4, max_len=64, block_len=8,
                prefill_chunk=8)
    base.update(kw)
    return serving.ServingSpec(**base)


@pytest.fixture(scope="module")
def prepared():
    spec = _spec()
    cfg = spec.apply_to(get_smoke_config(ARCH))
    params = init_params(jax.random.PRNGKey(0), cfg)
    return serving.prepare(params, spec, cfg=cfg)


@pytest.fixture(scope="module")
def trace16(prepared):
    return serving.make_poisson_trace(
        seed=0, num_requests=16, rate=1.0,
        vocab_size=prepared.cfg.vocab_size)


# --------------------------------------------------------------- parity
def _contiguous_logits(params, cfg, tokens, n_steps):
    """Greedy token-by-token decode through the contiguous cache;
    returns the logits at every step (the reference trajectory)."""
    caches = init_caches(cfg, 1, 64)
    feed = list(tokens)
    outs = []
    for i in range(len(tokens) + n_steps - 1):
        tok = jnp.asarray([[feed[i]]], jnp.int32)
        logits, caches = jax.jit(
            decode_step, static_argnames=("cfg",))(
                params, caches, tok, jnp.int32(i), cfg)
        outs.append(np.asarray(logits[0, 0], np.float64))
        if i + 1 >= len(tokens):
            feed.append(int(jnp.argmax(logits[0, 0])))
    return outs, feed[len(tokens):]


def _paged_logits(params, cfg, tokens, n_steps, *, block_len=8,
                  chunks=(3,), kv_qdtype=None, num_blocks=16):
    """The same trajectory through chunked prefill + paged decode."""
    caches = init_paged_caches(cfg, num_blocks + 1, block_len, 1,
                               kv_qdtype=kv_qdtype)
    width = 64 // block_len
    table = np.zeros((1, width), np.int32)
    need = (len(tokens) + n_steps - 1 + block_len - 1) // block_len
    table[0, :need] = np.arange(1, need + 1)
    outs = []
    off = 0
    for c in list(chunks) + [len(tokens) - sum(chunks)]:
        tok = jnp.asarray(tokens[off:off + c], jnp.int32)[None, :]
        logits, caches = paged_prefill_chunk(
            params, caches, tok, jnp.int32(off), jnp.asarray(table),
            jnp.int32(c), jnp.int32(0), cfg, block_len, kv_qdtype)
        for j in range(c):
            outs.append(np.asarray(logits[0, j], np.float64))
        off += c
    feed = int(jnp.argmax(jnp.asarray(outs[-1])))
    gen = [feed]
    for i in range(n_steps - 1):
        logits, caches = paged_decode_step(
            params, caches, jnp.asarray([[feed]], jnp.int32),
            jnp.asarray([len(tokens) + i], jnp.int32),
            jnp.asarray(table), jnp.asarray([True]), cfg, block_len,
            kv_qdtype)
        outs.append(np.asarray(logits[0, 0], np.float64))
        feed = int(jnp.argmax(logits[0, 0]))
        gen.append(feed)
    return outs, gen


@pytest.mark.parametrize("arch", ["internlm2_1_8b", "mamba2_2_7b"])
def test_paged_decode_matches_contiguous_fp32(arch):
    """Chunked prefill + paged decode == token-by-token contiguous
    decode, bitwise, prompt logits included (fp32)."""
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = [3, 17, 9, 41, 5, 28, 7]
    ref, ref_gen = _contiguous_logits(params, cfg, tokens, 4)
    got, got_gen = _paged_logits(params, cfg, tokens, 4, chunks=(3,))
    assert got_gen == ref_gen
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


def test_paged_decode_matches_contiguous_int8():
    """Same bitwise parity with int8-quantized weights: both paths
    contract identical quantized operands, so the cache layout is the
    only variable — and it must not change a single bit."""
    spec = _spec(qdtype="int8")
    cfg = spec.apply_to(get_smoke_config(ARCH))
    params = serving.prepare(
        init_params(jax.random.PRNGKey(0), cfg), spec, cfg=cfg).params
    tokens = [3, 17, 9, 41, 5]
    ref, ref_gen = _contiguous_logits(params, cfg, tokens, 3)
    got, got_gen = _paged_logits(params, cfg, tokens, 3, chunks=(2,))
    assert got_gen == ref_gen
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(r, g)


def test_quantized_kv_decode_close_to_fp32_kv():
    """int8 KV blocks (per-position/head scales) track the fp32 cache
    within quantization error and generate a full stream."""
    cfg = get_smoke_config(ARCH)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = [3, 17, 9, 41, 5]
    ref, _ = _paged_logits(params, cfg, tokens, 3, chunks=(2,))
    got, gen = _paged_logits(params, cfg, tokens, 3, chunks=(2,),
                             kv_qdtype="int8")
    assert len(gen) == 3
    ref_last = np.asarray(ref[-1])
    rel = (np.abs(np.asarray(got[-1]) - ref_last).max()
           / (np.abs(ref_last).max() + 1e-6))
    assert rel < 0.1, rel


@pytest.mark.slow
def test_paged_decode_parity_under_tp_mesh_subprocess():
    """Paged decode with a TP mesh installed matches the single-device
    paged reference: use-site ShardSpecs route the MLP linears through
    the mesh execution classes, the gate-up / fused-epilogue sites
    decline to their unfused paths, and none of it may change the
    generated stream."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root / "tests")])
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_axis_env, make_mesh
        from repro.models import init_params
        from repro.models.pjit_utils import use_axis_env
        from test_serving import _paged_logits

        assert jax.device_count() == 8
        cfg = get_smoke_config("internlm2_1_8b")
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = [3, 17, 9, 41, 5]
        ref, ref_gen = _paged_logits(params, cfg, tokens, 3, chunks=(2,))
        mesh = make_mesh((1, 8), ("data", "model"))
        with use_axis_env(make_axis_env(mesh)):
            got, got_gen = _paged_logits(params, cfg, tokens, 3,
                                         chunks=(2,))
        assert got_gen == ref_gen, (got_gen, ref_gen)
        for r, g in zip(ref, got):
            err = np.abs(np.asarray(g) - np.asarray(r)).max()
            scale = np.abs(np.asarray(r)).max() + 1e-6
            assert err / scale < 5e-5, err / scale
        print("TP_PAGED_PARITY_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "TP_PAGED_PARITY_OK" in r.stdout


# ------------------------------------------------------------ scheduler
def _req(rid, plen=5, new=4, arrival=0.0):
    return Request(rid=rid, prompt=tuple(range(1, plen + 1)),
                   max_new_tokens=new, arrival=arrival)


def test_reserve_admission_debits_promised_headroom():
    """Reserve admission must account for blocks *promised* to already
    admitted slots, not just the (lazily drained) free list — otherwise
    worst cases oversubscribe the pool and decode dies mid-request."""
    sched = PagedScheduler(slots=4, table_width=4, num_blocks=4,
                           block_len=4, admission="reserve")
    for rid in range(4):
        sched.enqueue(_req(rid))          # worst case 2 blocks each
    assert sched.admit_ready() == [0, 1]  # 4 // 2, NOT all four
    assert sched.headroom() == 0 and len(sched.free) == 4
    assert len(sched.waiting) == 2
    sched.ensure_blocks(0, 3)             # slot 0 materializes 1 block
    assert sched.headroom() == 0          # promise shrank with it
    sched.retire(0)
    assert sched.admit_ready() == [0]


def test_scheduler_rejects_impossible_requests():
    sched = PagedScheduler(slots=2, table_width=4, num_blocks=2,
                           block_len=4, admission="reserve")
    with pytest.raises(ValueError, match="blocks"):
        sched.enqueue(_req(0, plen=10, new=8))     # needs 5 > 2 blocks
    sched2 = PagedScheduler(slots=2, table_width=2, num_blocks=8,
                            block_len=4, admission="optimistic")
    with pytest.raises(ValueError, match="max_len"):
        sched2.enqueue(_req(0, plen=6, new=4))     # 9 positions > 8


def test_block_reuse_after_eviction():
    """Evicted blocks return to the pool and the evicted request's
    re-admission rebuilds its table row from scratch."""
    sched = PagedScheduler(slots=2, table_width=4, num_blocks=3,
                           block_len=4, admission="optimistic")
    sched.enqueue(_req(0, plen=8, new=2))
    sched.enqueue(_req(1, plen=8, new=2))
    assert sched.admit_ready() == [0, 1]
    assert sched.ensure_blocks(0, 7)       # slot 0 takes blocks 1, 2
    owned0 = list(sched.owned[0])
    # slot 1 needs 2 blocks for its prompt but only 1 is free: the
    # LIFO victim is slot 1 itself -> preempted, blocks freed
    assert not sched.ensure_blocks(1, 7)
    assert sched.slots[1] is None and sched.evictions == 1
    assert sched.preempted and len(sched.free) == 1
    # preempted requests are held while someone is running...
    assert sched.admit_ready() == []
    sched.retire(0)
    # ...and re-admit once capacity truly freed, reusing slot 0's blocks
    assert sched.admit_ready() == [0]
    assert sched.slots[0].req.rid == 1
    assert sched.ensure_blocks(0, 7)
    assert set(sched.owned[0]) <= set(owned0) | {3}


# --------------------------------------------------------------- engine
def test_engine_ragged_retirement(prepared):
    """Requests with different lengths retire independently; every
    stream has exactly its requested length."""
    reqs = [serving.Request(rid=i, prompt=tuple([7] * (2 + i)),
                            max_new_tokens=2 + 3 * i, arrival=0.0)
            for i in range(4)]
    report = serving.Engine(prepared).run(reqs)
    assert report.completed == 4
    by_rid = {s.rid: s for s in report.stats}
    for r in reqs:
        assert by_rid[r.rid].new_tokens == r.max_new_tokens
    # ragged: the short request must have finished before the longest
    assert by_rid[0].done_iter < by_rid[3].done_iter


def test_engine_beats_lockstep_on_poisson_trace(prepared, trace16):
    """THE acceptance criterion: on the seeded 16-request trace the
    continuous engine completes everything with strictly higher
    completed-requests-per-model-call than lockstep at equal width."""
    report = serving.Engine(prepared).run(trace16)
    base = serving.run_lockstep(prepared, trace16)
    assert report.completed == report.total == 16
    assert base.completed == 16
    assert report.completed_per_call > base.completed_per_call
    assert report.max_blocks_in_use <= report.num_blocks
    for s in report.stats:
        assert s.latency_s > 0 and s.tokens_per_s > 0


def test_engine_interleaving_deterministic(prepared, trace16):
    """Same seed, same trace -> identical token streams and identical
    model-call counts across runs (the scheduler has no hidden
    nondeterminism)."""
    r1 = serving.Engine(prepared).run(trace16)
    r2 = serving.Engine(prepared).run(trace16)
    assert [s.tokens for s in r1.stats] == [s.tokens for s in r2.stats]
    assert r1.model_calls == r2.model_calls
    assert r1.prefill_chunks == r2.prefill_chunks


def test_engine_eviction_transparent(prepared):
    """A tight block budget forces preemption under optimistic
    admission; recompute-preemption must reproduce the exact streams of
    a roomy run, and the budget must never be exceeded."""
    reqs = [serving.Request(rid=i, prompt=(5, 9, 13, 2, 11, 3, 8, 4),
                            max_new_tokens=8, arrival=0.0)
            for i in range(3)]
    roomy = serving.Engine(prepared).run(reqs)

    spec = _spec(slots=2, kv_blocks=3, admission="optimistic")
    tight_prep = serving.prepare(prepared.params, spec,
                                 cfg=prepared.cfg)
    tight = serving.Engine(tight_prep).run(reqs)
    assert tight.completed == 3
    assert tight.evictions > 0
    assert tight.max_blocks_in_use <= 3
    assert ([s.tokens for s in tight.stats]
            == [s.tokens for s in roomy.stats])


def test_engine_reserve_never_evicts_when_oversubscribed(prepared):
    """Reserve admission queues instead of evicting when worst cases
    exceed the pool (the headroom-accounting regression test, at the
    engine level)."""
    spec = _spec(slots=4, kv_blocks=4, block_len=8, admission="reserve")
    prep = serving.prepare(prepared.params, spec, cfg=prepared.cfg)
    reqs = [serving.Request(rid=i, prompt=(3, 1, 4, 1, 5, 9),
                            max_new_tokens=6, arrival=0.0)
            for i in range(4)]                 # worst case 2 blocks each
    report = serving.Engine(prep).run(reqs)
    assert report.completed == 4
    assert report.evictions == 0
    assert report.max_blocks_in_use <= 4


@pytest.mark.parametrize("arch", ["mamba2_2_7b", "jamba_1_5_large_398b"])
def test_engine_serves_ssm_archs_with_wide_slots(arch):
    """Regression: SSM caches are batch=slots, so a prefill chunk (batch
    1) must slice/scatter exactly the admitted slot's recurrent-state
    row.  Ran concurrently at slots=4, every request's stream must still
    match the contiguous single-request reference — any cross-slot state
    bleed diverges immediately."""
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prep = serving.prepare(params, _spec(slots=4), cfg=cfg)
    reqs = [serving.Request(rid=i,
                            prompt=tuple(3 + i + j for j in range(3 + i)),
                            max_new_tokens=3 + i, arrival=0.0)
            for i in range(3)]
    report = serving.Engine(prep).run(reqs)
    assert report.completed == 3
    by_rid = {s.rid: s for s in report.stats}
    for r in reqs:
        _, ref_gen = _contiguous_logits(params, cfg, list(r.prompt),
                                        r.max_new_tokens)
        assert list(by_rid[r.rid].tokens) == ref_gen, r.rid


def test_engine_sparse_arrivals_no_spurious_livelock(prepared):
    """Idle fast-forwarding jumps the simulated clock straight to the
    next absolute arrival timestamp; the no-progress guard must count
    work iterations, not the clock, or a late arrival (low --rate) trips
    'engine made no progress' before the request even lands."""
    reqs = [serving.Request(rid=0, prompt=(1, 2, 3), max_new_tokens=2,
                            arrival=0.0),
            serving.Request(rid=1, prompt=(4, 5), max_new_tokens=2,
                            arrival=1e6)]
    report = serving.Engine(prepared).run(reqs)
    assert report.completed == 2


def test_lockstep_latency_includes_queue_wait(prepared):
    """Lockstep stamps latency at arrival, not at slot admission: with
    one slot, the queued request's latency contains the first request's
    full service time — the same enqueue->done definition the Engine
    reports, so the gated p50/p99 rows compare like with like."""
    prep = serving.prepare(prepared.params, _spec(slots=1),
                           cfg=prepared.cfg)
    reqs = [serving.Request(rid=i, prompt=(2, 3, 4), max_new_tokens=4,
                            arrival=0.0) for i in range(2)]
    base = serving.run_lockstep(prep, reqs)
    assert base.completed == 2
    by_rid = {s.rid: s for s in base.stats}
    # both requests share one arrival stamp; rid 1 retires strictly later
    assert by_rid[1].latency_s > by_rid[0].latency_s


def test_kv_bytes_is_analytic_and_exact(prepared):
    """kv_bytes() must match the materialized pools byte-for-byte while
    allocating nothing (serve.py calls it right before run())."""
    engine = serving.Engine(prepared)
    want = sum(np.asarray(x).nbytes
               for x in jax.tree.leaves(engine._fresh_caches()))
    assert engine.kv_bytes() == want


def test_engine_int8_kv_serves_trace(prepared):
    spec = _spec(kv_qdtype="int8")
    prep = serving.prepare(prepared.params, spec, cfg=prepared.cfg)
    trace = serving.make_poisson_trace(
        seed=3, num_requests=5, vocab_size=prepared.cfg.vocab_size)
    report = serving.Engine(prep).run(trace)
    assert report.completed == 5


# ------------------------------------------------------------- prep API
def test_servingspec_validation():
    with pytest.raises(ValueError):
        serving.ServingSpec(layout="bogus")
    with pytest.raises(ValueError):
        serving.ServingSpec(static_scales=True)          # needs qdtype
    with pytest.raises(ValueError):
        serving.ServingSpec(qdtype="int4")
    with pytest.raises(ValueError):
        serving.ServingSpec(max_len=4, block_len=8)
    with pytest.raises(Exception):
        spec = serving.ServingSpec()
        spec.slots = 8                                   # frozen


def test_prepare_on_bare_leaf_matches_convert_layout():
    from repro.core.sparse_linear import SparsityConfig, convert_layout

    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    spec = serving.ServingSpec(layout="compressed", sparsity=(2, 4),
                               qdtype="int8")
    got = serving.prepare({"w": w}, spec).params
    cfg = SparsityConfig(n=2, m=4, mode="compressed")
    want = convert_layout({"w": w}, cfg, "compressed", quantize="int8")
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))


def test_deprecated_shims_are_removed():
    """The PR-6 warn-once shims are gone: ``convert_to_serving``,
    ``quantize_tree`` and ``calibrate_activation_scales`` no longer
    exist as public names (migration: ``serving.prepare`` /
    ``convert_layout``; internals live on underscore-prefixed)."""
    from repro.core import quantize as q, sparse_linear

    assert not hasattr(sparse_linear, "convert_to_serving")
    assert "convert_to_serving" not in sparse_linear.__all__
    assert not hasattr(q, "quantize_tree")
    assert not hasattr(q, "calibrate_activation_scales")
    # the internals the serving pipeline uses are still there
    assert callable(q._quantize_tree)
    assert callable(q._calibrate_activation_scales)
    # the warn-once channel itself survives for the plan() kwarg shim
    assert callable(q.warn_deprecated_once)


def test_prepare_static_scales_requires_calibration_inputs():
    spec = serving.ServingSpec(qdtype="int8", static_scales=True)
    with pytest.raises(ValueError, match="calib"):
        serving.prepare({"w": jnp.ones((8, 8))}, spec)


def test_prepare_static_scales_calibrates_sites(prepared):
    spec = _spec(qdtype="int8", static_scales=True)
    cfg = spec.apply_to(get_smoke_config(ARCH))
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 1,
                                cfg.vocab_size)
    prep = serving.prepare(params, spec, cfg=cfg, calib_tokens=tokens)
    assert prep.calibrated_sites > 0
    report = serving.Engine(prep).run(
        [serving.Request(rid=0, prompt=(1, 2, 3), max_new_tokens=3)])
    assert report.completed == 1


# ------------------------------------------------------------- perf gate
def test_check_regression_gates_serving_rows():
    from benchmarks.check_regression import (compare, parse_skip_markers,
                                             parse_smoke_csv)

    csv = ("serving_trace/continuous,us_p50=1000,us_p99=2000,tok_s=50.0\n"
           "kernel_x,us_dense=10\n"
           "serving_trace/lockstep,SKIP,whatever\n")
    rows = parse_smoke_csv(csv)
    assert rows["serving_trace/continuous"] == {"us_p50": 1000.0,
                                                "us_p99": 2000.0}
    baseline = {"serving_trace/continuous": {"us_p50": 500.0},
                "serving_trace/lockstep": {"us_p50": 500.0},
                "kernel_x": {"us_dense": 10.0}}
    failures, _ = compare(rows, baseline, 1.25,
                          skips=parse_skip_markers(csv))
    # continuous slowed 2x -> fails; lockstep SKIP-excused; kernel_x ok
    assert [f[0] for f in failures] == ["serving_trace/continuous"]
