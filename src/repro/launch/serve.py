"""Production serving launcher: thin adapter over ``repro.serving``.

    python -m repro.launch.serve --arch internlm2_1_8b --smoke \
        [--sparsity 2:4 --mode compressed|gather|rowwise] [--requests 16] \
        [--quantize int8|fp8] [--static-scales] [--kv-quantize int8|fp8] \
        [--kernel-backend auto|tpu|interpret|jnp] \
        [--autotune] [--mesh 2x4] \
        [--block-len 8] [--kv-blocks N] [--admission reserve|optimistic]

This module only parses flags: it builds a frozen
:class:`repro.serving.ServingSpec`, runs :func:`repro.serving.prepare`
(layout conversion -> weight quantization -> static-scale calibration ->
mesh placement, in that order), and hands the result to
:class:`repro.serving.Engine` — a genuine continuous-batching loop over
a paged KV cache: per-request block tables, per-slot positions (ragged
lengths retire independently), prefill chunks interleaved with batched
decode steps, and admission/eviction under the ``--kv-blocks`` budget.

Every projection still lowers through the kernel dispatch engine; the
``--quantize``, ``--static-scales``, ``--mesh``, ``--kernel-backend``
and ``--autotune`` semantics are unchanged from the lockstep era — they
are ServingSpec fields now.  ``--kv-quantize int8|fp8`` additionally
stores the KV block pools in the narrow dtype with per-(position, head)
scales, riding the same dtype-parametric scale machinery as weights.

Reported metrics are honest serving numbers: per-request tokens/sec
(generated tokens over that request's enqueue->done wall time), p50/p99
request latency, and completed-request throughput — NOT the old padded
``slot-tokens/s``, which counted idle slots and prompt re-feeding as
throughput.
"""

from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--artifact", default=None, metavar="ARTIFACT_DIR",
                    help="serve a converted checkpoint artifact "
                         "(python -m repro.launch.convert) instead of "
                         "random init; the artifact manifest supplies the "
                         "config and ServingSpec — layout/quantize flags "
                         "are ignored, --kernel-backend/--autotune/--mesh "
                         "still override")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sparsity", default=None)
    ap.add_argument("--mode", default="compressed",
                    choices=["dense", "compressed", "gather", "rowwise"])
    ap.add_argument("--quantize", default=None, choices=["int8", "fp8"],
                    help="quantize every linear's values to the narrow "
                         "dtype with per-channel scales (int8: VNNI "
                         "lineage; fp8: e4m3fn + fp32 accumulation)")
    ap.add_argument("--static-scales", action="store_true",
                    help="with --quantize: calibrate static activation "
                         "scales on one batch so decode skips the "
                         "per-row absmax pass")
    ap.add_argument("--kv-quantize", default=None, choices=["int8", "fp8"],
                    help="store the paged KV cache in the narrow dtype "
                         "with per-(position, head) scales")
    ap.add_argument("--mesh", default=None, metavar="DxM",
                    help="install a (data, model) mesh, e.g. 2x4 — run "
                         "kernels per-shard via shard_map (needs that many "
                         "devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (concurrent streams)")
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--block-len", type=int, default=8,
                    help="tokens per KV block")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="total KV block budget (default: enough for "
                         "every slot at --max-len; smaller values force "
                         "admission queueing / eviction)")
    ap.add_argument("--admission", default="reserve",
                    choices=["reserve", "optimistic"])
    ap.add_argument("--prefill-chunk", type=int, default=8)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrival rate (requests per scheduler "
                         "iteration)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "tpu", "interpret", "jnp"],
                    help="dispatch-engine backend override")
    ap.add_argument("--autotune", action="store_true",
                    help="autotune kernel block sizes (persisted under "
                         "experiments/autotune/)")
    ap.add_argument("--lockstep", action="store_true",
                    help="ALSO run the pre-paging lockstep loop on the "
                         "same trace and print the comparison")
    ap.add_argument("--explain", action="store_true",
                    help="print the static dispatch-plan audit for these "
                         "flags (weight-free; no serving run)")
    args = ap.parse_args()
    if args.static_scales and not args.quantize:
        ap.error("--static-scales requires --quantize int8|fp8")
    if not args.arch and not args.artifact:
        ap.error("need --arch (random init) or --artifact (converted "
                 "checkpoint)")

    import jax

    from repro import serving
    from repro.configs import get_config, get_smoke_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import init_params

    use_compile_cache()

    mesh = None
    if args.mesh:
        d_, m_ = map(int, args.mesh.lower().split("x"))
        mesh = (d_, m_)

    if args.artifact:
        backend = (args.kernel_backend if args.kernel_backend != "auto"
                   else None)
        if args.explain:
            from repro.analysis import audit_artifact
            audit = audit_artifact(args.artifact, backend=backend or "tpu")
            print("\n".join(audit.summary_lines()))
            return
        prepared = serving.prepare_from_artifact(
            args.artifact, backend=backend,
            autotune=args.autotune or None, mesh=mesh)
        spec, cfg = prepared.spec, prepared.cfg
        mesh = spec.mesh
        print(f"artifact {args.artifact}: config {cfg.name}, spec "
              f"{spec.layout}/{spec.sparsity}/{spec.qdtype}")
    else:
        sparsity = None
        if args.sparsity:
            n, m = map(int, args.sparsity.split(":"))
            sparsity = (n, m)
        spec = serving.ServingSpec(
            layout=args.mode, sparsity=sparsity, qdtype=args.quantize,
            static_scales=args.static_scales, mesh=mesh,
            backend=args.kernel_backend, autotune=args.autotune,
            slots=args.batch, max_len=args.max_len, block_len=args.block_len,
            kv_blocks=args.kv_blocks, kv_qdtype=args.kv_quantize,
            admission=args.admission, prefill_chunk=args.prefill_chunk)

        base_cfg = (get_smoke_config(args.arch) if args.smoke
                    else get_config(args.arch))
        if args.explain:
            # static plan audit: what will the engine run for these flags,
            # and why does anything fall off the kernel tier — no weights,
            # no serving loop (see python -m repro.launch.audit)
            from repro.analysis import audit_model
            backend = (args.kernel_backend if args.kernel_backend != "auto"
                       else "tpu")
            audit = audit_model(base_cfg, spec, backend=backend,
                                arch=args.arch)
            print("\n".join(audit.summary_lines()))
            return

        cfg = spec.apply_to(base_cfg)
        params = init_params(jax.random.PRNGKey(0), cfg)
        calib_tokens = None
        if args.static_scales:
            calib_tokens = jax.random.randint(
                jax.random.PRNGKey(2), (args.batch, min(args.max_len, 32)),
                1, cfg.vocab_size)
        prepared = serving.prepare(params, spec, cfg=cfg,
                                   calib_tokens=calib_tokens)
    if prepared.calibrated_sites:
        print(f"static activation scales calibrated for "
              f"{prepared.calibrated_sites} linear site(s) — decode skips "
              f"the per-row absmax pass")
    nbytes = sum(x.size * x.dtype.itemsize
                 for x in jax.tree.leaves(prepared.params))
    sp_str = (f"{spec.sparsity[0]}:{spec.sparsity[1]}" if spec.sparsity
              else "dense")
    print(f"serving {cfg.name}: {nbytes/1e6:.1f} MB weights "
          f"({sp_str}/{spec.layout}"
          f"{'/' + spec.qdtype if spec.qdtype else ''})")
    if mesh:
        print(f"mesh installed: data={mesh[0]} x model={mesh[1]} "
              f"({prepared.mesh.devices.size} devices)")

    if args.autotune:
        from repro.kernels import autotune as kautotune
        from repro.kernels import dispatch as kdispatch
        from repro.kernels.registry import resolve_backend

        # the decode loop is jitted (tracers only): tune eagerly up front
        with prepared.activate():
            tuned = kdispatch.pretune(prepared.params, spec.slots,
                                      cfg.sparsity, prepared.dispatch)
        if tuned:
            store = kautotune.store_path(resolve_backend(args.kernel_backend))
            print(f"autotuned {tuned} linear problem(s) -> {store}")
        else:
            print("autotune: nothing to tune "
                  "(jnp-routed, unfittable, or cache already warm)")
    print("dispatch engine plan:")
    for line in prepared.dispatch_report():
        print(line)

    engine = serving.Engine(prepared)
    print(f"paged KV: {engine.num_blocks} block(s) x {spec.block_len} "
          f"tokens, {engine.kv_bytes()/1e6:.1f} MB pools, "
          f"admission={spec.admission}")
    trace = serving.make_poisson_trace(
        seed=args.seed, num_requests=args.requests, rate=args.rate,
        new_mix=((args.new_tokens, 1.0),), vocab_size=cfg.vocab_size)
    report = engine.run(trace)
    print(f"served {report.describe()}")
    per_req = ", ".join(f"r{s.rid}:{s.tokens_per_s:.1f}"
                        for s in report.stats[:8])
    print(f"per-request tokens/s: {per_req}"
          f"{' ...' if len(report.stats) > 8 else ''}")
    print(f"completed-request throughput: "
          f"{report.completed_per_call:.3f} requests/model-call, "
          f"{report.completed / report.wall_s:.2f} requests/s")
    if args.lockstep:
        base = serving.run_lockstep(prepared, trace)
        print(f"lockstep baseline: {base.describe()}")
        print(f"continuous vs lockstep requests/model-call: "
              f"{report.completed_per_call:.3f} vs "
              f"{base.completed_per_call:.3f}")


if __name__ == "__main__":
    main()
