#!/usr/bin/env python3
"""Serve internlm2-1.8B at published widths on a TPU, end to end.

    python chip_smoke.py              # one chip: dense and 2:4 serving
    python chip_smoke.py --chips 4    # tensor-parallel 2:4 serving, 4 chips
    python chip_smoke.py --smoke      # rehearsal on the CPU (see below)

One chip.  For the dense 4:4 layout and then the 2:4 ``compressed``
layout, random weights made from ``--seed`` go through
``repro.serving.prepare``; every decode and prefill linear site must plan
a compiled (``tpu``) kernel — checked with ``Prepared.audit`` and the
dispatch report of the prepared tree — and ``Engine.run`` serves 8 seeded
requests (64-token prompts in one 64-token prefill chunk, 32 new tokens
each, 8 slots), all of which must complete.  For 2:4, one prefill chunk's
logits and one decode step's logits from the kernel tier are compared
with the jnp reference tier on the same params and cache state.

Four chips (``--chips 4``, only this phase).  The 2:4 model serves the
same requests under a (1, 4) (data, model) mesh; its greedy tokens and
prefill logits are compared with the same model on device 0 alone, and
each device's memory is printed — in bfloat16, and again in float32,
where the greedy tokens must be identical.

The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed phase exits non-zero before it, and so does a run that finds no
TPU.  ``--smoke`` rehearses the same phases on the CPU at smoke widths
with interpret-mode kernels (for ``--chips 4`` give the CPU four devices
with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``); it never
prints the result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

ARCH = "internlm2_1_8b"
SLOTS = 8
PROMPT = 64          # prompt tokens = prefill chunk: one prefill shape
NEW = 32             # generated tokens per request
BLOCK_LEN = 16
# normalised max error, max|kernel - reference| / max|reference|, of the
# bf16 logits of the kernel tier against the jnp reference tier
PARITY_BOUND = 2e-2
# the same error of a float32 model on a mesh against one chip
TP_F32_BOUND = 1e-3


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def normalised_error(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(want))):
        fail("non-finite logits")
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def device_phase(args):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)}")
    if not args.smoke and d0.platform != "tpu":
        fail(f"no TPU: JAX found platform {d0.platform!r}")
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} devices, "
             f"found {len(devices)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def make_spec(args, layout, sparsity, mesh=None):
    from repro.serving import ServingSpec

    return ServingSpec(layout=layout, sparsity=sparsity, mesh=mesh,
                       backend="interpret" if args.smoke else "auto",
                       slots=SLOTS, max_len=PROMPT + NEW,
                       block_len=BLOCK_LEN, prefill_chunk=PROMPT)


def init_model(args, spec, dtype="bfloat16"):
    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.models import init_params

    base = get_smoke_config(ARCH) if args.smoke else get_config(ARCH)
    cfg = dataclasses.replace(spec.apply_to(base), dtype=dtype)
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)
    return params, cfg


def trace(args, cfg, n, new):
    from repro.serving import make_poisson_trace

    return make_poisson_trace(seed=args.seed, num_requests=n, rate=float(n),
                              prompt_mix=((PROMPT, 1.0),),
                              new_mix=((new, 1.0),),
                              vocab_size=cfg.vocab_size)


def check_plan(prepared, expected_backend):
    """Every decode and prefill linear site plans a kernel on the
    expected backend: the static audit's reason codes, and the dispatch
    report of the prepared tree itself."""
    from repro.kernels.dispatch import JNP_REFERENCE, describe
    from repro.kernels.registry import resolve_backend

    resolved = resolve_backend(prepared.spec.backend)
    if resolved != expected_backend:
        fail(f"backend {prepared.spec.backend!r} resolves to {resolved!r}, "
             f"not {expected_backend!r}")
    report = prepared.dispatch_report()
    print("dispatch report:")
    for line in report:
        print(line)
    audit = prepared.audit(backend=resolved)
    sites = [s for s in audit.sites
             if s.phase in ("decode", "prefill")
             and not s.path.startswith("attention/")]
    off = [f"{s.phase} {s.path}: {describe(s.decision)}" for s in sites
           if not s.decision.uses_kernel or s.decision.backend != resolved]
    off += [line for line in report if JNP_REFERENCE in line]
    codes = sorted({c for s in sites for c in s.codes})
    print(f"plan: {len(sites)} decode/prefill linear site(s), "
          f"{len(off)} off the {resolved} kernel tier; codes {codes}")
    if not sites or off:
        fail("linear sites off the kernel tier:\n  " + "\n  ".join(off))


def serve(args, prepared, label):
    """Warm up (compiles one prefill and one decode shape), then serve
    SLOTS requests; every request must finish with NEW tokens."""
    from repro.serving import Engine

    engine = Engine(prepared)
    t0 = time.perf_counter()
    engine.run(trace(args, prepared.cfg, 1, 2))
    warm_s = time.perf_counter() - t0
    report = engine.run(trace(args, prepared.cfg, SLOTS, NEW))
    full = sum(1 for s in report.stats if s.new_tokens == NEW)
    print(f"{label}: served {report.describe()}")
    print(f"{label}: {full}/{SLOTS} requests completed with {NEW} tokens")
    if report.completed != SLOTS or full != SLOTS:
        fail(f"{label}: {full}/{SLOTS} requests completed")
    return report, warm_s


def prefill_logits(prepared, prompt):
    """Logits of one prefill chunk into slot 0 from empty pools, with the
    resulting cache state and block table.  Runs under the caller's
    ``prepared.activate()``."""
    import jax.numpy as jnp

    from repro.models.paged import init_paged_caches, paged_prefill_chunk
    from repro.serving import Engine

    spec, cfg, params = prepared.spec, prepared.cfg, prepared.params
    width = spec.table_width
    table = np.zeros((spec.slots, width), np.int32)
    table[0] = np.arange(1, width + 1)
    caches = init_paged_caches(cfg, Engine(prepared).num_blocks + 1,
                               spec.block_len, spec.slots)
    lp, caches = paged_prefill_chunk(
        params, caches, prompt, jnp.int32(0), jnp.asarray(table[:1]),
        jnp.int32(PROMPT), jnp.int32(0), cfg, spec.block_len)
    return np.asarray(lp[0], np.float32), caches, table


def decode_logits(prepared, caches, table, token):
    """Logits of slot 0's next decode step over ``caches``."""
    import jax.numpy as jnp

    from repro.models.paged import paged_decode_step

    spec = prepared.spec
    feed = np.zeros((spec.slots, 1), np.int32)
    feed[0, 0] = token
    positions = np.zeros((spec.slots,), np.int32)
    positions[0] = PROMPT
    active = np.zeros((spec.slots,), bool)
    active[0] = True
    ld, _ = paged_decode_step(
        prepared.params, caches, jnp.asarray(feed), jnp.asarray(positions),
        jnp.asarray(table), jnp.asarray(active), prepared.cfg,
        spec.block_len)
    return np.asarray(ld[0, 0], np.float32)


def parity_phase(args, prepared):
    """Kernel tier vs ``use_dispatch(backend="jnp")`` on the same params
    and cache state.  The dispatch backend is read while tracing and the
    jitted steps do not key on it, so the compiled programs are dropped
    before and after the reference tier."""
    import jax

    from repro.kernels.dispatch import use_dispatch

    prompt = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                (1, PROMPT), 1, prepared.cfg.vocab_size)
    jax.clear_caches()
    with prepared.activate():
        lp_k, caches_k, table = prefill_logits(prepared, prompt)
        token = int(np.argmax(lp_k[PROMPT - 1]))
        ld_k = decode_logits(prepared, caches_k, table, token)
    jax.clear_caches()
    with prepared.activate(), use_dispatch(backend="jnp"):
        lp_r, _, _ = prefill_logits(prepared, prompt)
        ld_r = decode_logits(prepared, caches_k, table, token)
    jax.clear_caches()
    err_p = normalised_error(lp_k, lp_r)
    err_d = normalised_error(ld_k, ld_r)
    print(f"parity 2:4 kernel vs jnp: prefill logits {err_p:.3e}, "
          f"decode logits {err_d:.3e} (bound {PARITY_BOUND:.0e})")
    if max(err_p, err_d) > PARITY_BOUND:
        fail(f"kernel-vs-jnp parity {max(err_p, err_d):.3e} > "
             f"{PARITY_BOUND:.0e}")
    return err_p, err_d


def memory_line(device) -> str:
    stats = device.memory_stats() or {}
    if "bytes_in_use" not in stats:
        return f"{device}: memory stats not reported"
    return (f"{device}: bytes_in_use={stats['bytes_in_use']} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def one_chip(args):
    import jax

    from repro import serving

    expected = "interpret" if args.smoke else "tpu"
    summary = []
    for layout, sparsity in (("dense", None), ("compressed", (2, 4))):
        label = f"{layout}" + (f" {sparsity[0]}:{sparsity[1]}"
                               if sparsity else " 4:4")
        spec = make_spec(args, layout, sparsity)
        t0 = time.perf_counter()
        params, cfg = init_model(args, spec)
        prepared = serving.prepare(params, spec, cfg=cfg)
        del params
        jax.block_until_ready(prepared.params)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(prepared.params))
        print(f"{label}: {cfg.name} {cfg.num_layers} layers, d_model "
              f"{cfg.d_model}, vocab {cfg.vocab_size}: {nbytes} weight "
              f"bytes, made in {time.perf_counter() - t0:.1f}s")
        check_plan(prepared, expected)
        report, warm_s = serve(args, prepared, label)
        errs = parity_phase(args, prepared) if sparsity else None
        summary.append((label, warm_s, report, errs))
        del prepared     # free this tree before the next one is built
        gc.collect()
    for label, warm_s, report, errs in summary:
        print(f"report {label}: warm-up (compile included) {warm_s:.2f}s, "
              f"run {report.wall_s:.3f}s, {report.completed}/{SLOTS} "
              f"requests completed, {report.generated_tokens} tokens"
              + (f", parity prefill {errs[0]:.3e} decode {errs[1]:.3e}"
                 if errs else ""))
    print("report " + memory_line(jax.devices()[0]))


def weights_per_device(params):
    import jax

    held = {d: 0 for d in jax.devices()}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return held


def tensor_parallel(args, dtype):
    """2:4 serving under a (1, chips) mesh vs the same model on device 0.

    In bfloat16 the two placements round differently (the mesh psums
    fp32 partials and XLA fuses the unsharded ops differently), so a
    near-tied argmax may flip and the greedy streams diverge from there:
    the logits are held to PARITY_BOUND and token agreement is reported.
    In float32 (run under full float32 matmul passes, see ``main``) the
    rounding gap is far below any top-2 margin, so the greedy tokens of
    every request must be identical."""
    import jax

    from repro import serving

    spec1 = make_spec(args, "compressed", (2, 4))
    params, cfg = init_model(args, spec1, dtype)
    one = serving.prepare(params, spec1, cfg=cfg)
    label = f"{dtype} 2:4"
    report1, _ = serve(args, one, f"one chip {label}")
    prompt = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                (1, PROMPT), 1, cfg.vocab_size)
    with one.activate():
        lp1, _, _ = prefill_logits(one, prompt)
    del one
    jax.clear_caches()

    tp = serving.prepare(params, dataclasses.replace(
        spec1, mesh=(1, args.chips)), cfg=cfg)
    del params       # the one-chip copy: device 0 now holds its share only
    gc.collect()
    held = weights_per_device(tp.params)
    for d in jax.devices():
        print(f"{label} weights on {d}: {held[d]} bytes; {memory_line(d)}")
    if sum(1 for b in held.values() if b > 0) < args.chips:
        fail("the mesh placed weights on fewer devices than it holds")
    check_plan(tp, "interpret" if args.smoke else "tpu")
    report4, _ = serve(args, tp, f"mesh (1, {args.chips}) {label}")
    with tp.activate():
        lp4, _, _ = prefill_logits(tp, prompt)
    del tp
    jax.clear_caches()
    gc.collect()

    toks1 = {s.rid: s.tokens for s in report1.stats}
    toks4 = {s.rid: s.tokens for s in report4.stats}
    same = sum(1 for rid in toks1 if toks1[rid] == toks4.get(rid))
    err = normalised_error(lp4, lp1)
    bound = PARITY_BOUND if dtype == "bfloat16" else TP_F32_BOUND
    print(f"report tensor-parallel {label} vs one chip: {same}/{SLOTS} "
          f"requests with identical greedy tokens; prefill logits error "
          f"{err:.3e} (bound {bound:.0e}); weights per device "
          f"{[held[d] for d in jax.devices()]}")
    if err > bound:
        fail(f"tensor-parallel {label} prefill logits error {err:.3e}")
    if dtype == "float32" and same != SLOTS:
        fail(f"tensor-parallel {label} greedy tokens differ in "
             f"{SLOTS - same} request(s)")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the tensor-parallel phase")
    ap.add_argument("--smoke", action="store_true",
                    help="rehearse on the CPU at smoke widths with "
                         "interpret-mode kernels; prints no result line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import use_compile_cache

    print(f"compilation cache: {use_compile_cache()}")
    device = device_phase(args)
    if args.chips == 1:
        one_chip(args)
    else:
        tensor_parallel(args, "bfloat16")
        # the TPU's default float32 matmul is one bfloat16 pass: ask for
        # full float32 passes in XLA's dots and the kernels' alike
        with jax.default_matmul_precision("highest"):
            tensor_parallel(args, "float32")
        for d in jax.devices():
            print("report " + memory_line(d))
    if args.smoke:
        print("rehearsal passed (no result line: --smoke runs no chip)")
        return
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
