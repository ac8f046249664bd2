"""Unified sparse-GEMM dispatch engine — one entry point for every mode.

This is the software realization of the paper's vertically-integrated
engine: models, the serving launcher, examples, and benchmarks all call
:func:`sparse_matmul`, and ONE dispatch layer decides — per (mode, shape,
N:M, dtype, backend) — whether the matmul runs on a Pallas kernel
(``tile_gemm`` for dense 4:4, ``nm_spmm`` for Tier-1 compressed,
``nm_spmm_gather`` for Tier-2 lane-aligned) or on the documented pure-jnp
reference formulation.

The jnp formulations remain first-class: they are the semantics the
kernels are tested against, and they are what the engine uses whenever
kernels don't apply — on the backward pass (the Pallas bodies carry no
VJP rules, so every kernel call is a ``jax.custom_vjp`` whose backward
is the reference's VJP), on CPU by default (interpret-mode Pallas is
emulation, not perf), or when a shape fails a kernel's tiling
constraints.

Under an installed mesh env the engine no longer surrenders to XLA: when
the use-site supplies a :class:`ShardSpec` (how TP/FSDP slices the
(b, ke, o) GEMM), the engine computes the **per-shard local problem**,
fits blocks against it, and runs the selected Pallas kernel inside
``jax.shard_map`` — partial products over a sharded
contraction dim are combined with ``psum``; an out-dim-sharded GEMM needs
no collective.  The jnp reference remains the fallback whenever the local
shape doesn't fit a kernel or a spec slices the N:M metadata axis
non-divisibly.

dtype is a dispatch axis with THREE execution classes: float, int8, and
fp8.  Quantized layouts (an extra per-channel ``"scale"`` leaf next to
narrow values — see ``repro.core.quantize``) plan on their storage dtype
(``int8`` or ``float8_e4m3fn``) and resolve to the matching ``*_int8`` /
``*_fp8`` kernel entries, which quantize activations per row on the way
in (against a calibrated static ``act_scale`` when the leaf carries one
— decode skips the absmax pass), pad odd row counts up to the 32-row
narrow-dtype sublane quantum, contract narrow x narrow into the wide
accumulator (int32 for int8, fp32 for fp8 via
``preferred_element_type``), and dequantize once on the way out.  The
jnp dequantize-reference formulation is their fallback — under
``jax.grad``, when the quantized tiling constraints don't fit
(quantized contraction blocks are multiples of the 32-row sublane
quantum), and for fp8 on TPUs without a native fp8 MXU dot
(``registry.fp8_native_dot``; interpret mode always emulates).  Under
a use-site ``ShardSpec`` the quantized entries run per-shard like the
float ones: the weight-scale leaf gets its own PartitionSpec (out-dim
axes), activations quantize inside the shard body, and a sharded
contraction psums the **raw accumulator partials** (shards share one
row scale via a pmax of local absmaxes) before the single dequantize on
the gathered result.  Autotune cache keys carry the dtype, so the three
execution classes of one problem shape never share tuned blocks.

Block sizes come from the autotuner (in-process cache + JSON store under
``experiments/autotune/``, keyed by device kind) when enabled, else from
per-problem fitting.

``docs/architecture.md`` walks the full dispatch lifecycle (ShardSpec ->
plan -> fit_blocks -> shard_map body -> psum/dequantize) and catalogs
every fallback reason string this module can emit.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import math
import types
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import nm
from repro.core import quantize as quant
from repro.core.ste import srste_prune
from repro.kernels import autotune, registry
from repro.kernels import epilogue as epilib
from repro.kernels import reasons
from repro.kernels.reasons import ReasonCode
from repro.kernels.actsparse import ActivationSpec, apply_mask, block_maps
from repro.kernels.epilogue import Epilogue
from repro.kernels.registry import (KernelEntry, dtype_name,
                                    largest_fitting_block)

__all__ = [
    "ActivationSpec",
    "DispatchConfig",
    "DispatchDecision",
    "GemmProblem",
    "ShardSpec",
    "shard_spec_from_env",
    "sparse_matmul",
    "gate_up_matmul",
    "requant_plan",
    "requant_decision",
    "ReasonCode",
    "attention",
    "plan",
    "describe",
    "use_dispatch",
    "current_dispatch",
    "input_features",
    "iter_linear_leaves",
    "iter_linear_items",
    "plan_for",
    "pretune",
    "dispatch_report",
    "JNP_REFERENCE",
]

JNP_REFERENCE = "jnp-reference"

_log = logging.getLogger(__name__)

Blocks = Tuple[int, int, int]  # (block_b, block_ke, block_o)


@dataclasses.dataclass(frozen=True)
class DispatchConfig:
    """Engine-wide knobs; override per call-site or via ``use_dispatch``."""

    backend: str = "auto"          # auto | tpu | interpret | jnp
    autotune: bool = False         # time block candidates on first sight
    blocks: Optional[Blocks] = None  # hard override (block_b, block_ke, block_o)
    persist_autotune: bool = True  # write tuned blocks to the JSON store


_DEFAULT = DispatchConfig()


def current_dispatch() -> DispatchConfig:
    return _DEFAULT


@contextlib.contextmanager
def use_dispatch(**overrides):
    """Temporarily override the engine defaults (tests, serving flags)."""
    global _DEFAULT
    prev = _DEFAULT
    _DEFAULT = dataclasses.replace(prev, **overrides)
    try:
        yield _DEFAULT
    finally:
        _DEFAULT = prev


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How the active mesh slices one (b, ke, o) GEMM at its use site.

    Each field is a mesh axis name (or tuple of names) sharding that dim,
    or ``None`` for replicated.  Built from the use-site gather hint +
    the installed :class:`AxisEnv` by :func:`shard_spec_from_env`:
    column-parallel weights shard ``o`` on the model axis (no collective),
    row-parallel weights shard ``ke`` (partial products need a ``psum``),
    FSDP shards only the batch dim (weight replicated at use-site).
    """

    mesh: Any                      # jax.sharding.Mesh
    batch: Any = None              # axes sharding the flattened batch dim
    ke: Any = None                 # axes sharding the contraction dim
    o: Any = None                  # axes sharding the out-features dim

    def axis_size(self, axes) -> int:
        if axes is None:
            return 1
        if isinstance(axes, str):
            axes = (axes,)
        return math.prod(self.mesh.shape[a] for a in axes)

    @property
    def shards(self) -> Tuple[int, int, int]:
        return (self.axis_size(self.batch), self.axis_size(self.ke),
                self.axis_size(self.o))

    @property
    def collective(self) -> str:
        return "psum" if self.axis_size(self.ke) > 1 else "none"


def shard_spec_from_env(gather: Optional[str] = None) -> Optional[ShardSpec]:
    """ShardSpec for the installed mesh env, or ``None`` without one.

    ``gather`` is the use-site parallelism hint ("col" | "row" | None,
    same vocabulary as ``apply_linear``).  Call sites with no hint (e.g.
    expert linears already inside a shard_map body) must NOT build a spec
    — nesting shard_map is not supported — so only hinted sites get one.
    """
    try:
        from repro.models.pjit_utils import axis_env
    except (ImportError, AttributeError) as e:  # pragma: no cover
        _warn_mesh_probe_once(e)
        return None
    env = axis_env()
    if env is None:
        return None
    batch = env.physical("batch")
    if gather == "col":
        return ShardSpec(mesh=env.mesh, batch=batch, o=env.model_axis)
    if gather == "row":
        return ShardSpec(mesh=env.mesh, batch=batch, ke=env.model_axis)
    return ShardSpec(mesh=env.mesh, batch=batch)


@dataclasses.dataclass(frozen=True)
class GemmProblem:
    """ONE value object describing a GEMM the engine may plan.

    This is the canonical input to :func:`plan`: every dispatch axis —
    execution mode, global (b, ke, o) shape, N:M geometry, storage
    dtype, autodiff/mesh context, epilogue lattice point, dual gate-up
    pairing, and the dynamic ``activation`` sparsity point
    (``ActivationSpec.point``) — lives on the one frozen object, so
    ``plan``, ``plan_for``, ``pretune``, the dispatch report, and the
    autotune cache key are all derived from the same problem identity
    and cannot drift.  The legacy ``plan(mode, b=..., ...)`` kwarg
    spelling still works through a warn-once shim.

    ``differentiating`` plans the backward pass of a site (always the
    jnp reference's VJP — see :func:`_reference_vjp`); the static plan
    auditor's ``grad`` phase sets it.

    ``epilogue`` and ``activation`` are the *canonical point strings*
    (``EpilogueSpec.point`` / ``ActivationSpec.point``), not the operand
    -carrying objects — a problem is an identity, not an execution.
    """

    mode: str
    b: int
    ke: int
    o: int
    n: int = 4
    m: int = 4
    dtype: Any = jnp.float32
    differentiating: bool = False
    sharded: bool = False
    shard: Optional[ShardSpec] = None
    static_scales: bool = False
    epilogue: Optional[str] = None
    dual: bool = False
    activation: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class DispatchDecision:
    """What the engine chose for one problem, and why.

    ``blocks_source`` is the structured origin of ``blocks`` —
    "none" (jnp reference), "fitted" (per-problem default fitting),
    "tuned" (autotune cache hit), or "pinned" (config override).  Logic
    branches on it; ``reason`` is display text only, rendered from the
    frozen :class:`repro.kernels.reasons.ReasonCode` catalog.

    ``reason_code`` is the machine-readable identity of ``reason``: a
    fallback code (jnp tier) or a blocks-provenance code (kernel tier).
    ``epilogue_reason`` / ``activation_reason`` carry the structured
    counterpart of ``epilogue_fused`` / ``activation_skip`` — fused or
    why not, skip or why mask-only — so the static plan auditor
    (:mod:`repro.analysis`) can gate on declines without parsing text.

    ``placement`` is the execution class: "single" (one device / XLA owns
    any layout) or "shard_map" (kernel runs per-shard under the mesh; the
    local problem is ``local_dims`` and partial products are combined by
    ``collective``).
    """

    mode: str
    backend: str
    kernel: str                    # registry entry name or JNP_REFERENCE
    blocks: Optional[Blocks]
    reason: str
    blocks_source: str = "none"    # none | fitted | tuned | pinned
    placement: str = "single"      # single | shard_map
    local_dims: Optional[Tuple[int, int, int]] = None  # per-shard (b, ke, o)
    shards: Optional[Tuple[int, int, int]] = None      # mesh split of (b, ke, o)
    collective: Optional[str] = None                   # psum | none
    act_scales: Optional[str] = None   # quantized entries: dynamic | static
    dtype: Optional[str] = None    # canonical execution dtype the plan ran on
    epilogue: Optional[str] = None     # requested lattice point (EpilogueSpec.point)
    epilogue_fused: bool = False       # True: kernel flush applies it in VMEM
    activation: Optional[str] = None   # activation-sparsity point (ActivationSpec.point)
    activation_skip: bool = False      # True: kernel elides dead K-blocks in-kernel
    reason_code: Optional[ReasonCode] = None       # catalog identity of ``reason``
    epilogue_reason: Optional[ReasonCode] = None   # fused, or why not
    activation_reason: Optional[ReasonCode] = None  # skip, or why mask-only
    mux: Optional[str] = None      # nm_spmm family's M:1 mux: slab | rows

    @property
    def uses_kernel(self) -> bool:
        return self.kernel != JNP_REFERENCE

    @property
    def uses_shard_map(self) -> bool:
        return self.placement == "shard_map"


def _epi_annotation(d: DispatchDecision) -> str:
    if d.epilogue_reason is not None:
        return reasons.epilogue_annotation(d.epilogue_reason)
    if not d.uses_kernel:
        return "jnp"
    return "fused" if d.epilogue_fused else "jnp"


def _act_annotation(d: DispatchDecision) -> str:
    if d.activation_reason is not None:
        return reasons.activation_annotation(d.activation_reason)
    if not d.uses_kernel:
        return "jnp"
    return "skip" if d.activation_skip else "mask-only"


def describe(d: DispatchDecision) -> str:
    if not d.uses_kernel:
        base = f"{d.mode}: {JNP_REFERENCE} ({d.reason})"
        if d.epilogue is not None:
            base += f" epilogue={d.epilogue}[{_epi_annotation(d)}]"
        if d.activation is not None:
            base += f" activation={d.activation}[{_act_annotation(d)}]"
        return base
    bb, bke, bo = d.blocks
    base = (f"{d.mode}: {d.kernel}[{d.backend}] "
            f"blocks=(b={bb},ke={bke},o={bo})")
    if d.dtype is not None:
        base += f" dtype={d.dtype}"
    if d.mux is not None:
        base += f" mux={d.mux}"
    if d.epilogue is not None:
        base += f" epilogue={d.epilogue}[{_epi_annotation(d)}]"
    if d.activation is not None:
        base += f" activation={d.activation}[{_act_annotation(d)}]"
    if d.uses_shard_map:
        lb, lke, lo = d.local_dims
        sb, ske, so = d.shards
        base += (f" shard_map[{d.collective}]"
                 f" shards=(b/{sb},ke/{ske},o/{so})"
                 f" local=(b={lb},ke={lke},o={lo})")
    if d.act_scales is not None:
        base += f" act-scales={d.act_scales}"
    return f"{base} ({d.reason})"


# ---------------------------------------------------------------------------
# jnp reference formulations (the engine's always-available fallback tier)
# ---------------------------------------------------------------------------

def _deq(params, w):
    """Dequantize-reference semantics for int8 layouts: the float operand
    the kernel-free path (and autodiff) contracts against."""
    if quant.SCALE_KEY in params:
        return quant.dequantize(w, params[quant.SCALE_KEY])
    return w


def _jnp_dense(x2, params, cfg, g):
    w = _deq(params, params["w"])
    if cfg.mode == "masked" and cfg.is_sparse:
        w = srste_prune(w, cfg.n, cfg.m, cfg.srste_lam)
    return x2 @ g(w).astype(x2.dtype)


def _jnp_compressed(x2, params, cfg, g):
    meta = nm.unpack_meta(params["meta_packed"])
    w = nm.decompress(g(_deq(params, params["values"])), meta, cfg.n, cfg.m)
    return x2 @ w.astype(x2.dtype)


def _jnp_gather(x2, params, cfg, g):
    idx = params["gather_idx"]
    kc = idx.shape[0]
    blk = (jnp.arange(kc, dtype=jnp.int32) // cfg.n) * cfg.m
    x_g = jnp.take(x2, blk + idx, axis=-1)
    return x_g @ g(_deq(params, params["values"])).astype(x2.dtype)


_JNP_IMPL: Dict[str, Callable] = {
    "dense": _jnp_dense,
    "masked": _jnp_dense,
    "compressed": _jnp_compressed,
    "gather": _jnp_gather,
}


# ---------------------------------------------------------------------------
# Kernel adapters + registry entries
# ---------------------------------------------------------------------------

_BB_CAPS = (256, 128, 64, 32)
_BO_CAPS = (256, 128, 64)
_BKE_CAPS = (1024, 512, 256, 128)

# Mosaic tiles the second-to-last (sublane) dim of a block in 8-row
# quanta unless the block spans the whole array dim
_SUBLANE = 8


def _fit_rows(b: int, cap: int) -> Optional[int]:
    """Row (sublane) block for ``b`` rows: all of them when ``b <= cap``
    (a block equal to the array dim is always legal), else the largest
    divisor ``<= cap`` on the 8-row sublane quantum — ``b=200`` tiles as
    40, not 100, which Mosaic refuses.  ``None`` when no divisor is on
    the quantum (the engine then falls back to the jnp reference)."""
    if b <= cap:
        return b
    return largest_fitting_block(b, cap, _SUBLANE)


def _enumerate(b, ke, o, ke_multiple):
    out = []
    for cb in _BB_CAPS:
        for co in _BO_CAPS:
            for ck in _BKE_CAPS:
                bb = _fit_rows(b, cb)
                bo = largest_fitting_block(o, co)
                bke = largest_fitting_block(ke, ck, ke_multiple)
                if bb and bo and bke and (bb, bke, bo) not in out:
                    out.append((bb, bke, bo))
    return out


def _is_int8(dtype) -> bool:
    return jnp.dtype(dtype) == jnp.int8


def _is_fp8(dtype) -> bool:
    return jnp.dtype(dtype) == jnp.float8_e4m3fn


# the narrow dtypes (int8, fp8) pack 4x more values per 32-bit lane
# register than fp32, so the sublane quantum of a quantized operand tile
# is 32 rows (vs 8 for fp32) — quantized contraction blocks must be
# multiples of 32, and the float entries decline quantized problems
# outright (casting would break the storage model).
_Q_SUBLANE = 32


def _fit_tile_gemm(b, ke, o, n, m, dtype):
    if quant.is_quantized_dtype(dtype):
        return None
    bb = _fit_rows(b, 128)
    bo = largest_fitting_block(o, 128)
    bke = largest_fitting_block(ke, 512)
    if bb is None or bo is None or bke is None:
        return None
    return (bb, bke, bo)


def _epi_kwargs(epilogue: Optional[Epilogue]) -> Dict[str, Any]:
    """Kernel kwargs for a fused epilogue lattice point (empty = bare
    flush).  Only reaches the kernel when the plan said
    ``epilogue_fused`` — fallback paths apply ``epilib.apply_reference``
    on the result instead."""
    if epilogue is None or epilogue.spec.is_identity:
        return {}
    return {"epilogue": epilogue.spec, "bias": epilogue.bias,
            "requant_scale": epilogue.requant_scale}


def _run_tile_gemm(x2, params, cfg, g, blocks, interpret, out_dtype,
                   epilogue=None, activation=None):
    from repro.kernels.tile_gemm.kernel import tile_gemm, tile_gemm_masked

    bb, bke, bo = blocks
    w = g(params["w"]).astype(x2.dtype)
    if activation is not None:
        # x2 is already masked (sparse_matmul applies the mask pass on
        # every route); the skip maps only elide dead-block work
        kmap, kmask = block_maps(x2, bb, bke)
        return tile_gemm_masked(x2, w, kmap, kmask,
                                block_b=bb, block_k=bke, block_o=bo,
                                out_dtype=out_dtype, interpret=interpret,
                                **_epi_kwargs(epilogue))
    return tile_gemm(x2, w, block_b=bb, block_k=bke, block_o=bo,
                     out_dtype=out_dtype, interpret=interpret,
                     **_epi_kwargs(epilogue))


def _nm_ke_multiple(n: int) -> int:
    # nm_spmm packs meta 4 rows/byte: block_kc = block_ke*n/4 must be a
    # positive multiple of 4 -> block_ke*n % 16 == 0.
    return 16 // math.gcd(n, 16)


def _fit_nm_spmm(b, ke, o, n, m, dtype):
    if m != 4 or quant.is_quantized_dtype(dtype):
        return None  # kernel fixes M=4 (paper's detailed design)
    bb = _fit_rows(b, 128)
    bo = largest_fitting_block(o, 128)
    bke = largest_fitting_block(ke, 512, _nm_ke_multiple(n))
    if bb is None or bo is None or bke is None:
        return None
    return (bb, bke, bo)


def _run_nm_spmm(x2, params, cfg, g, blocks, interpret, out_dtype,
                 epilogue=None, activation=None):
    from repro.kernels.nm_spmm.kernel import nm_spmm, nm_spmm_masked

    bb, bke, bo = blocks
    v = g(params["values"]).astype(x2.dtype)
    if activation is not None:
        kmap, kmask = block_maps(x2, bb, bke)
        return nm_spmm_masked(x2, v, params["meta_packed"], kmap, kmask,
                              cfg.n, block_b=bb, block_o=bo, block_ke=bke,
                              out_dtype=out_dtype, interpret=interpret,
                              **_epi_kwargs(epilogue))
    return nm_spmm(x2, v, params["meta_packed"], cfg.n,
                   block_b=bb, block_o=bo, block_ke=bke,
                   out_dtype=out_dtype, interpret=interpret,
                   **_epi_kwargs(epilogue))


def _fit_nm_gather(b, ke, o, n, m, dtype):
    if m != 4 or quant.is_quantized_dtype(dtype):
        return None
    bb = _fit_rows(b, 128)
    bo = largest_fitting_block(o, 128)
    # kernel reshapes the activation tile into 4-row blocks: block_ke % 4 == 0
    bke = largest_fitting_block(ke, 512, 4)
    if bb is None or bo is None or bke is None:
        return None
    return (bb, bke, bo)


def _run_nm_gather(x2, params, cfg, g, blocks, interpret, out_dtype,
                   epilogue=None, activation=None):
    from repro.kernels.nm_spmm_gather.kernel import (
        nm_spmm_gather_bk, nm_spmm_gather_bk_masked)

    bb, bke, bo = blocks
    v = g(params["values"]).astype(x2.dtype)
    idx = params["gather_idx"].reshape(-1, 1)
    # bk layout: natural (B, K_eff) in / (B, O) out — the row gather and
    # both transposes live in the kernel's index map, so no permuted
    # activation copy is ever materialized in HBM
    if activation is not None:
        kmap, kmask = block_maps(x2, bb, bke)
        return nm_spmm_gather_bk_masked(
            x2, v, idx, kmap, kmask, cfg.n,
            block_b=bb, block_o=bo, block_ke=bke,
            out_dtype=out_dtype, interpret=interpret,
            **_epi_kwargs(epilogue))
    return nm_spmm_gather_bk(x2, v, idx, cfg.n,
                             block_b=bb, block_o=bo, block_ke=bke,
                             out_dtype=out_dtype, interpret=interpret,
                             **_epi_kwargs(epilogue))


# --- fused gate-up (dual) adapters: ONE pallas_call reads the
# activation tile once, contracts it against BOTH same-shaped weights,
# and emits silu(g) * u (the "silu_mul" epilogue point) directly.
# Registered as ``run_dual`` on the same entries; plans with
# ``dual=True`` only fuse when the selected entry carries one.

def _dual_epi_kwargs(epilogue: Optional[Epilogue]) -> Dict[str, Any]:
    # the dual kernels default to the bare silu_mul point; only a
    # requant extension needs operands (bias is unsupported on duals)
    if epilogue is None:
        return {}
    return {"epilogue": epilogue.spec,
            "requant_scale": epilogue.requant_scale}


def _run_tile_gemm_dual(x2, pg, pu, cfg, g, blocks, interpret, out_dtype,
                        epilogue=None):
    from repro.kernels.tile_gemm.kernel import tile_gemm_dual

    bb, bke, bo = blocks
    return tile_gemm_dual(x2, g(pg["w"]).astype(x2.dtype),
                          g(pu["w"]).astype(x2.dtype),
                          block_b=bb, block_k=bke, block_o=bo,
                          out_dtype=out_dtype, interpret=interpret,
                          **_dual_epi_kwargs(epilogue))


def _run_nm_spmm_dual(x2, pg, pu, cfg, g, blocks, interpret, out_dtype,
                      epilogue=None):
    from repro.kernels.nm_spmm.kernel import nm_spmm_dual

    bb, bke, bo = blocks
    return nm_spmm_dual(x2, g(pg["values"]).astype(x2.dtype),
                        pg["meta_packed"],
                        g(pu["values"]).astype(x2.dtype),
                        pu["meta_packed"], cfg.n,
                        block_b=bb, block_o=bo, block_ke=bke,
                        out_dtype=out_dtype, interpret=interpret,
                        **_dual_epi_kwargs(epilogue))


def _run_nm_gather_dual(x2, pg, pu, cfg, g, blocks, interpret, out_dtype,
                        epilogue=None):
    from repro.kernels.nm_spmm_gather.kernel import nm_spmm_gather_dual_bk

    bb, bke, bo = blocks
    return nm_spmm_gather_dual_bk(
        x2, g(pg["values"]).astype(x2.dtype),
        pg["gather_idx"].reshape(-1, 1),
        g(pu["values"]).astype(x2.dtype),
        pu["gather_idx"].reshape(-1, 1), cfg.n,
        block_b=bb, block_o=bo, block_ke=bke,
        out_dtype=out_dtype, interpret=interpret,
        **_dual_epi_kwargs(epilogue))


registry.register(KernelEntry(
    name="tile_gemm", mode="dense", activation_skip=True,
    fit_blocks=_fit_tile_gemm, run=_run_tile_gemm,
    run_dual=_run_tile_gemm_dual,
    candidates=lambda b, ke, o, n, m, dtype: _enumerate(b, ke, o, 1),
))
registry.register(KernelEntry(
    name="nm_spmm", mode="compressed", activation_skip=True,
    fit_blocks=_fit_nm_spmm, run=_run_nm_spmm,
    run_dual=_run_nm_spmm_dual,
    candidates=lambda b, ke, o, n, m, dtype: _enumerate(
        b, ke, o, _nm_ke_multiple(n)),
))
registry.register(KernelEntry(
    name="nm_spmm_gather", mode="gather", activation_skip=True,
    fit_blocks=_fit_nm_gather, run=_run_nm_gather,
    run_dual=_run_nm_gather_dual,
    candidates=lambda b, ke, o, n, m, dtype: _enumerate(b, ke, o, 4),
))


# --- quantized entries (int8 VNNI lineage + fp8 e4m3fn): narrow values
# x narrow row-quantized activations contracted into the wide
# accumulator (int32 / fp32), dequantized once on the way out.
# Registered at higher priority; their fit_blocks only accept problems
# of their own storage dtype, so float dispatch is untouched and the
# two quantized classes never collide.

def _q_ke_multiple(n: int) -> int:
    # the compressed values tile (block_kc = block_ke*n/4 rows) must hit
    # the 32-row narrow-dtype sublane quantum: block_ke*n % 128 == 0.
    # This also covers meta packing (block_ke*n % 16) and the
    # dense/gather cases.
    return (4 * _Q_SUBLANE) // math.gcd(n, 4 * _Q_SUBLANE)


def _q_padded_b(b: int) -> int:
    """Row count of the quantized activation tile after final-block
    padding.

    The quantized activation operand is narrow too, so its sublane (row)
    axis carries the same 32-row quantum as the values tile.  Rather than
    rejecting row counts off the quantum — which would throw every odd
    decode batch (e.g. b=3) back to the dequantize reference — the run
    adapters zero-pad the final row block up to the quantum and slice the
    output back; blocks are fitted against the padded row count.
    """
    return b + (-b) % _Q_SUBLANE


def _quantize_acts(x2, params, dtype):
    """Narrow activations + (B, 1) scales: static (calibrated) when the
    leaf carries an ``act_scale``, else the dynamic per-row absmax pass.
    ``dtype`` is the layout's storage dtype (int8 | fp8) — activations
    quantize to the same class the weights live in.

    Activations that arrive ALREADY narrow were requantized by the
    producing kernel's fused epilogue against THIS leaf's calibrated
    static scale — reuse them as-is and rebuild the (B, 1) row scales
    from that scalar (the whole point of the fused requant: the
    quantize pass here disappears)."""
    if jnp.dtype(x2.dtype) == jnp.dtype(dtype):
        if quant.ACT_SCALE_KEY not in params:
            raise ValueError(
                "pre-quantized activations need a calibrated act_scale "
                "on the consuming leaf (the fused requant quantized "
                "against it)")
        s = jnp.asarray(params[quant.ACT_SCALE_KEY],
                        jnp.float32).reshape(())
        return x2, jnp.full((x2.shape[0], 1), s, jnp.float32)
    if quant.ACT_SCALE_KEY in params:
        return quant.quantize_rows_static(x2, params[quant.ACT_SCALE_KEY],
                                          dtype)
    return quant.quantize_rows(x2, dtype=dtype)


def _pad_rows(xq, xs, b_pad: int):
    """Zero-pad quantized rows to the narrow sublane quantum (padded rows
    contract to zero and are sliced off the output)."""
    pad = b_pad - xq.shape[0]
    if pad == 0:
        return xq, xs
    xq = jnp.pad(xq, ((0, pad), (0, 0)))
    xs = jnp.pad(xs, ((0, pad), (0, 0)), constant_values=1.0)
    return xq, xs


def _fit_q_rows(b: int):
    return largest_fitting_block(_q_padded_b(b), 128, _Q_SUBLANE)


def _fit_dense_q(b, ke, o):
    bb = _fit_q_rows(b)
    bo = largest_fitting_block(o, 128)
    bke = largest_fitting_block(ke, 512, _Q_SUBLANE)
    if bb is None or bo is None or bke is None:
        return None
    return (bb, bke, bo)


def _fit_nm_q(b, ke, o, n):
    bb = _fit_q_rows(b)
    bo = largest_fitting_block(o, 128)
    bke = largest_fitting_block(ke, 512, _q_ke_multiple(n))
    if bb is None or bo is None or bke is None:
        return None
    return (bb, bke, bo)


def _fit_tile_gemm_int8(b, ke, o, n, m, dtype):
    return _fit_dense_q(b, ke, o) if _is_int8(dtype) else None


def _fit_tile_gemm_fp8(b, ke, o, n, m, dtype):
    return _fit_dense_q(b, ke, o) if _is_fp8(dtype) else None


def _fit_nm_spmm_int8(b, ke, o, n, m, dtype):
    if m != 4 or not _is_int8(dtype):
        return None
    return _fit_nm_q(b, ke, o, n)


def _fit_nm_spmm_fp8(b, ke, o, n, m, dtype):
    if m != 4 or not _is_fp8(dtype):
        return None
    return _fit_nm_q(b, ke, o, n)


def _fit_nm_gather_int8(b, ke, o, n, m, dtype):
    if m != 4 or not _is_int8(dtype):
        return None
    return _fit_nm_q(b, ke, o, n)


def _fit_nm_gather_fp8(b, ke, o, n, m, dtype):
    if m != 4 or not _is_fp8(dtype):
        return None
    return _fit_nm_q(b, ke, o, n)


def _dense_q_kernel(dtype):
    from repro.kernels.tile_gemm.kernel import tile_gemm_fp8, tile_gemm_int8

    return tile_gemm_fp8 if _is_fp8(dtype) else tile_gemm_int8


def _nm_q_kernel(dtype):
    from repro.kernels.nm_spmm.kernel import nm_spmm_fp8, nm_spmm_int8

    return nm_spmm_fp8 if _is_fp8(dtype) else nm_spmm_int8


def _gather_q_kernel(dtype):
    from repro.kernels.nm_spmm_gather.kernel import (nm_spmm_gather_fp8,
                                                     nm_spmm_gather_int8)

    return nm_spmm_gather_fp8 if _is_fp8(dtype) else nm_spmm_gather_int8


def _run_tile_gemm_q(x2, params, cfg, g, blocks, interpret, out_dtype,
                     epilogue=None, activation=None):
    bb, bke, bo = blocks
    b = x2.shape[0]
    qdt = params["w"].dtype
    xq, xs = _pad_rows(*_quantize_acts(x2, params, qdt), _q_padded_b(b))
    ws = params[quant.SCALE_KEY].reshape(1, -1)
    if activation is not None:
        from repro.kernels.tile_gemm.kernel import tile_gemm_masked

        # maps come from the PADDED narrow rows: zeros quantize to zero
        # (and padding rows ARE zero), so dead blocks stay detectable
        kmap, kmask = block_maps(xq, bb, bke)
        y = tile_gemm_masked(xq, g(params["w"]), kmap, kmask, xs, ws,
                             acc_dtype=_dual_q_acc(qdt),
                             block_b=bb, block_k=bke, block_o=bo,
                             out_dtype=out_dtype, interpret=interpret,
                             **_epi_kwargs(epilogue))
        return y[:b]
    y = _dense_q_kernel(qdt)(xq, g(params["w"]), xs, ws,
                             block_b=bb, block_k=bke, block_o=bo,
                             out_dtype=out_dtype, interpret=interpret,
                             **_epi_kwargs(epilogue))
    return y[:b]


def _partial_tile_gemm_q(xq, params, cfg, blocks, interpret):
    bb, bke, bo = blocks
    return _dense_q_kernel(params["w"].dtype)(
        xq, params["w"], block_b=bb, block_k=bke, block_o=bo,
        interpret=interpret)


def _run_nm_spmm_q(x2, params, cfg, g, blocks, interpret, out_dtype,
                   epilogue=None, activation=None):
    bb, bke, bo = blocks
    b = x2.shape[0]
    qdt = params["values"].dtype
    xq, xs = _pad_rows(*_quantize_acts(x2, params, qdt), _q_padded_b(b))
    ws = params[quant.SCALE_KEY].reshape(1, -1)
    if activation is not None:
        from repro.kernels.nm_spmm.kernel import nm_spmm_masked

        kmap, kmask = block_maps(xq, bb, bke)
        y = nm_spmm_masked(xq, g(params["values"]), params["meta_packed"],
                           kmap, kmask, cfg.n, xs, ws,
                           acc_dtype=_dual_q_acc(qdt),
                           block_b=bb, block_o=bo, block_ke=bke,
                           out_dtype=out_dtype, interpret=interpret,
                           **_epi_kwargs(epilogue))
        return y[:b]
    y = _nm_q_kernel(qdt)(xq, g(params["values"]), params["meta_packed"],
                          xs, ws, cfg.n,
                          block_b=bb, block_o=bo, block_ke=bke,
                          out_dtype=out_dtype, interpret=interpret,
                          **_epi_kwargs(epilogue))
    return y[:b]


def _partial_nm_spmm_q(xq, params, cfg, blocks, interpret):
    bb, bke, bo = blocks
    return _nm_q_kernel(params["values"].dtype)(
        xq, params["values"], params["meta_packed"], None, None, cfg.n,
        block_b=bb, block_o=bo, block_ke=bke, interpret=interpret)


def _run_nm_gather_q(x2, params, cfg, g, blocks, interpret, out_dtype,
                     epilogue=None, activation=None):
    from repro.kernels.nm_spmm_gather.kernel import (
        nm_spmm_gather_bk, nm_spmm_gather_bk_masked)

    bb, bke, bo = blocks
    b = x2.shape[0]
    qdt = params["values"].dtype
    xq, xs = _pad_rows(*_quantize_acts(x2, params, qdt), _q_padded_b(b))
    ws = params[quant.SCALE_KEY].reshape(1, -1)
    idx = params["gather_idx"].reshape(-1, 1)
    # bk layout (see _run_nm_gather): no xq.T / y_t.T HBM round trips
    if activation is not None:
        kmap, kmask = block_maps(xq, bb, bke)
        y = nm_spmm_gather_bk_masked(
            xq, g(params["values"]), idx, kmap, kmask, cfg.n, xs, ws,
            acc_dtype=jnp.int32 if _is_int8(qdt) else jnp.float32,
            block_b=bb, block_o=bo, block_ke=bke,
            out_dtype=out_dtype, interpret=interpret,
            **_epi_kwargs(epilogue))
        return y[:b]
    y = nm_spmm_gather_bk(xq, g(params["values"]), idx, cfg.n, xs, ws,
                          acc_dtype=jnp.int32 if _is_int8(qdt)
                          else jnp.float32,
                          block_b=bb, block_o=bo, block_ke=bke,
                          out_dtype=out_dtype, interpret=interpret,
                          **_epi_kwargs(epilogue))
    return y[:b]


def _partial_nm_gather_q(xq, params, cfg, blocks, interpret):
    bb, bke, bo = blocks
    idx = params["gather_idx"].reshape(-1, 1)
    y_t = _gather_q_kernel(params["values"].dtype)(
        xq.T, params["values"], idx, None, None, cfg.n,
        block_b=bb, block_o=bo, block_ke=bke, interpret=interpret)
    return y_t.T


# --- fused gate-up (dual) quantized adapters (see the float duals
# above the float registrations): one x read, one quantize pass.

def _dual_q_acc(qdt):
    return jnp.int32 if _is_int8(qdt) else jnp.float32


def _run_tile_gemm_dual_q(x2, pg, pu, cfg, g, blocks, interpret, out_dtype,
                          epilogue=None):
    from repro.kernels.tile_gemm.kernel import tile_gemm_dual

    bb, bke, bo = blocks
    b = x2.shape[0]
    qdt = pg["w"].dtype
    # one x read, one quantize pass: the gate leaf's scale quantizes the
    # shared activations (both sites calibrated on the same tensor)
    xq, xs = _pad_rows(*_quantize_acts(x2, pg, qdt), _q_padded_b(b))
    y = tile_gemm_dual(xq, g(pg["w"]), g(pu["w"]), xs,
                       pg[quant.SCALE_KEY].reshape(1, -1),
                       pu[quant.SCALE_KEY].reshape(1, -1),
                       acc_dtype=_dual_q_acc(qdt),
                       block_b=bb, block_k=bke, block_o=bo,
                       out_dtype=out_dtype, interpret=interpret,
                       **_dual_epi_kwargs(epilogue))
    return y[:b]


def _run_nm_spmm_dual_q(x2, pg, pu, cfg, g, blocks, interpret, out_dtype,
                        epilogue=None):
    from repro.kernels.nm_spmm.kernel import nm_spmm_dual

    bb, bke, bo = blocks
    b = x2.shape[0]
    qdt = pg["values"].dtype
    xq, xs = _pad_rows(*_quantize_acts(x2, pg, qdt), _q_padded_b(b))
    y = nm_spmm_dual(xq, g(pg["values"]), pg["meta_packed"],
                     g(pu["values"]), pu["meta_packed"], cfg.n, xs,
                     pg[quant.SCALE_KEY].reshape(1, -1),
                     pu[quant.SCALE_KEY].reshape(1, -1),
                     acc_dtype=_dual_q_acc(qdt),
                     block_b=bb, block_o=bo, block_ke=bke,
                     out_dtype=out_dtype, interpret=interpret,
                     **_dual_epi_kwargs(epilogue))
    return y[:b]


def _run_nm_gather_dual_q(x2, pg, pu, cfg, g, blocks, interpret, out_dtype,
                          epilogue=None):
    from repro.kernels.nm_spmm_gather.kernel import nm_spmm_gather_dual_bk

    bb, bke, bo = blocks
    b = x2.shape[0]
    qdt = pg["values"].dtype
    xq, xs = _pad_rows(*_quantize_acts(x2, pg, qdt), _q_padded_b(b))
    y = nm_spmm_gather_dual_bk(
        xq, g(pg["values"]), pg["gather_idx"].reshape(-1, 1),
        g(pu["values"]), pu["gather_idx"].reshape(-1, 1), cfg.n, xs,
        pg[quant.SCALE_KEY].reshape(1, -1),
        pu[quant.SCALE_KEY].reshape(1, -1),
        acc_dtype=_dual_q_acc(qdt),
        block_b=bb, block_o=bo, block_ke=bke,
        out_dtype=out_dtype, interpret=interpret,
        **_dual_epi_kwargs(epilogue))
    return y[:b]


def _q_candidates(b, ke, o, ke_multiple):
    cands = _enumerate(_q_padded_b(b), ke, o, ke_multiple)
    return [c for c in cands if c[0] % _Q_SUBLANE == 0] or cands


registry.register(KernelEntry(
    name="tile_gemm_int8", mode="dense", priority=10, activation_skip=True,
    fit_blocks=_fit_tile_gemm_int8, run=_run_tile_gemm_q,
    run_dual=_run_tile_gemm_dual_q,
    quantized=True, run_quantized=_partial_tile_gemm_q,
    candidates=lambda b, ke, o, n, m, dtype: _q_candidates(
        b, ke, o, _Q_SUBLANE),
))
registry.register(KernelEntry(
    name="nm_spmm_int8", mode="compressed", priority=10, activation_skip=True,
    fit_blocks=_fit_nm_spmm_int8, run=_run_nm_spmm_q,
    run_dual=_run_nm_spmm_dual_q,
    quantized=True, run_quantized=_partial_nm_spmm_q,
    candidates=lambda b, ke, o, n, m, dtype: _q_candidates(
        b, ke, o, _q_ke_multiple(n)),
))
registry.register(KernelEntry(
    name="nm_spmm_gather_int8", mode="gather", priority=10, activation_skip=True,
    fit_blocks=_fit_nm_gather_int8, run=_run_nm_gather_q,
    run_dual=_run_nm_gather_dual_q,
    quantized=True, run_quantized=_partial_nm_gather_q,
    candidates=lambda b, ke, o, n, m, dtype: _q_candidates(
        b, ke, o, _q_ke_multiple(n)),
))
registry.register(KernelEntry(
    name="tile_gemm_fp8", mode="dense", priority=10, activation_skip=True,
    fit_blocks=_fit_tile_gemm_fp8, run=_run_tile_gemm_q,
    run_dual=_run_tile_gemm_dual_q,
    quantized=True, run_quantized=_partial_tile_gemm_q,
    supported=registry.supports_fp8,
    candidates=lambda b, ke, o, n, m, dtype: _q_candidates(
        b, ke, o, _Q_SUBLANE),
))
registry.register(KernelEntry(
    name="nm_spmm_fp8", mode="compressed", priority=10, activation_skip=True,
    fit_blocks=_fit_nm_spmm_fp8, run=_run_nm_spmm_q,
    run_dual=_run_nm_spmm_dual_q,
    quantized=True, run_quantized=_partial_nm_spmm_q,
    supported=registry.supports_fp8,
    candidates=lambda b, ke, o, n, m, dtype: _q_candidates(
        b, ke, o, _q_ke_multiple(n)),
))
registry.register(KernelEntry(
    name="nm_spmm_gather_fp8", mode="gather", priority=10, activation_skip=True,
    fit_blocks=_fit_nm_gather_fp8, run=_run_nm_gather_q,
    run_dual=_run_nm_gather_dual_q,
    quantized=True, run_quantized=_partial_nm_gather_q,
    supported=registry.supports_fp8,
    candidates=lambda b, ke, o, n, m, dtype: _q_candidates(
        b, ke, o, _q_ke_multiple(n)),
))


# --- flash attention: mode "attention", dims mapped as (b, ke, o) =
# (T_q, T_k, head_dim), blocks = (block_q, block_k, head_dim).  The last
# kernel that used to be called directly by model code now routes through
# the same registry/plan machinery as the GEMMs.

def _fit_flash(b, ke, o, n, m, dtype):
    bq = _fit_rows(b, 256)
    bk = _fit_rows(ke, 256)
    if bq is None or bk is None or o % 8 != 0:
        return None
    return (bq, bk, o)


def _flash_candidates(b, ke, o, n, m, dtype):
    out = []
    for cq in (256, 128):
        for ck in (256, 128):
            bq = _fit_rows(b, cq)
            bk = _fit_rows(ke, ck)
            if bq and bk and (bq, bk, o) not in out:
                out.append((bq, bk, o))
    return out


def _run_flash(x2, params, cfg, g, blocks, interpret, out_dtype):
    from repro.kernels.flash_attention.ops import flash_attention_op

    bq, bk, _ = blocks
    return flash_attention_op(params["q"], params["k"], params["v"],
                              causal=cfg.causal, block_q=bq, block_k=bk,
                              interpret=interpret)


registry.register(KernelEntry(
    name="flash_attention", mode="attention",
    fit_blocks=_fit_flash, run=_run_flash,
    candidates=_flash_candidates,
))


# ---------------------------------------------------------------------------
# Planning + execution
# ---------------------------------------------------------------------------

def _mode_of(params: Dict[str, Any], cfg) -> str:
    if "w" in params:
        return "masked" if (cfg.mode == "masked" and cfg.is_sparse) else "dense"
    if "meta_packed" in params:
        return "compressed"
    if "gather_idx" in params:
        return "gather"
    raise ValueError(f"unrecognized linear params: {list(params)}")


def _problem_dims(mode: str, params: Dict[str, Any], x) -> Tuple[int, int]:
    """(ke, o): the contraction length the kernel sees and out features."""
    if mode in ("dense", "masked"):
        return params["w"].shape
    # compressed and gather both contract over x's trailing K_eff
    return x.shape[-1], params["values"].shape[1]


def input_features(params: Dict[str, Any], cfg) -> int:
    """Expected trailing dim of ``x`` for these params (K_eff)."""
    mode = _mode_of(params, cfg)
    if mode in ("dense", "masked"):
        return params["w"].shape[0]
    return params["values"].shape[0] * cfg.m // cfg.n


def _reference_vjp(kernel_fn: Callable, reference_fn: Callable, *operands):
    """``kernel_fn(*operands)``, differentiated as ``reference_fn``.

    Pallas bodies carry no VJP rules, so each kernel call is a
    ``jax.custom_vjp`` at the kernel boundary: the forward pass runs the
    kernel, the backward pass is the VJP of the jnp reference
    formulation on the same operands — the AUTODIFF fallback that
    ``GemmProblem.differentiating`` plans.  ``operands`` are pytrees of
    arrays; both functions close over static values only.
    """
    f = jax.custom_vjp(kernel_fn)
    f.defvjp(lambda *ops: (kernel_fn(*ops), ops),
             lambda ops, ct: jax.vjp(reference_fn, *ops)[1](ct))
    return f(*operands)


_mesh_probe_warned = False


def _warn_mesh_probe_once(err: BaseException) -> None:
    global _mesh_probe_warned
    if not _mesh_probe_warned:
        _mesh_probe_warned = True
        _log.warning(
            "repro.models.pjit_utils unavailable (%s): dispatch engine "
            "assumes no mesh env is installed", err)


def _mesh_active() -> bool:
    # Narrow except: a broken pjit_utils used to be swallowed silently,
    # masking real import errors as "no mesh".  Anything other than the
    # module/attr being absent should propagate.
    try:
        from repro.models.pjit_utils import axis_env
    except (ImportError, AttributeError) as e:
        _warn_mesh_probe_once(e)
        return False
    return axis_env() is not None


def _meta_axis_sliceable(mode: str, ke: int, n: int, m: int, ske: int) -> bool:
    """Can the contraction dim be cut into ``ske`` shards without splitting
    N:M metadata structure?

    compressed: each shard's values rows (ke_local*n/m) must pack whole
    meta bytes (4 rows/byte) -> ke*n % (4*m*ske) == 0.
    gather: shard boundaries must align with M-blocks so local gather
    indices stay block-relative -> ke % (m*ske) == 0.
    """
    if ske <= 1:
        return True
    if mode == "compressed":
        return (ke * n) % (4 * m * ske) == 0
    if mode == "gather":
        return ke % (m * ske) == 0
    return ke % ske == 0


def _cache_key(name: str, p: GemmProblem, dims: Tuple[int, int, int],
               fused: bool, skip: bool) -> str:
    """THE autotune key for one (entry, problem) pair — built from the
    GemmProblem so plan(), the concrete-autotune path, and the shard_map
    tuner can never disagree about problem identity.  ``dims`` is the
    shape the kernel body actually runs (per-shard local under
    shard_map); a fused epilogue changes the flush cost and an in-kernel
    block skip changes the traversal, so both suffix the key."""
    return autotune.cache_key(
        name, dims[0], dims[1], dims[2], p.n, p.m, p.dtype,
        epilogue=p.epilogue if fused else None,
        activation=p.activation if skip else None)


def plan(
    problem,
    *,
    dispatch: Optional[DispatchConfig] = None,
    **legacy,
) -> DispatchDecision:
    """Pure decision function: what would the engine run for this problem?

    The canonical form takes ONE :class:`GemmProblem` — every dispatch
    axis lives on the frozen value object::

        plan(GemmProblem("compressed", b=8, ke=1024, o=512, n=2,
                         dtype=jnp.int8, epilogue="bias+silu"),
             dispatch=dcfg)

    The legacy spelling ``plan(mode, b=..., ke=..., ...)`` still works —
    the kwargs are folded into a GemmProblem behind a warn-once
    ``DeprecationWarning``.

    ``problem.shard`` describes how the active mesh slices the problem
    at its use site; with one, the engine plans the third execution
    class — ``shard_map`` over the registry kernel — fitting blocks
    against the per-shard local shape.  ``sharded`` without a spec (mesh
    installed but the call-site gave no PartitionSpecs) still falls back
    to jnp.  Quantized problems (int8 | fp8) keep the shard_map class
    too: the per-channel weight scale rides along as an extra leaf with
    its own PartitionSpec and activations quantize inside the shard
    body.  ``static_scales`` records whether the use-site carries
    calibrated activation scales (decode skips the per-row absmax pass);
    it only annotates the decision.

    ``epilogue`` is the requested lattice point (``EpilogueSpec.point``,
    e.g. ``"bias+silu"``); the decision carries it back with
    ``epilogue_fused`` saying whether the kernel's flush applies it in
    VMEM.  Fusion needs a single-placement kernel decision — shard_map
    bodies psum BEFORE the epilogue may run, and the jnp tier applies
    the reference formulation — so every other route reports ``[jnp]``
    and the caller applies ``apply_reference``.  ``dual`` marks a fused
    gate-up (two same-shaped weights, one activation read); it
    additionally requires the selected entry to carry a ``run_dual``
    kernel.

    ``activation`` is the dynamic activation-sparsity point
    (``ActivationSpec.point``).  The mask pass is applied to ``x`` on
    every route (it is the semantics of the execution class), so the
    decision only reports whether the selected kernel additionally
    *skips* dead K-blocks in-kernel (``activation_skip``) — which needs
    a single-placement, non-dual decision on an entry whose adapter
    carries a masked variant.  Declining the skip never changes
    numerics.
    """
    if isinstance(problem, str):
        quant.warn_deprecated_once(
            "plan(mode, b=..., ke=..., ...)",
            "plan(GemmProblem(mode, b=..., ke=..., ...), dispatch=...)")
        problem = GemmProblem(mode=problem, **legacy)
    elif legacy:
        raise TypeError(
            "plan(GemmProblem, ...) accepts no per-axis kwargs — put "
            f"{sorted(legacy)} on the GemmProblem")
    p = problem
    dcfg = dispatch or _DEFAULT
    backend = registry.resolve_backend(dcfg.backend)
    dt_name = dtype_name(p.dtype)
    shard = p.shard

    def _jnp(code, **ctx):
        return DispatchDecision(
            p.mode, "jnp", JNP_REFERENCE, None, reasons.render(code, **ctx),
            dtype=dt_name, epilogue=p.epilogue, activation=p.activation,
            reason_code=code,
            epilogue_reason=(ReasonCode.EPILOGUE_JNP_TIER
                             if p.epilogue is not None else None),
            activation_reason=(ReasonCode.ACT_MASK_ONLY_JNP
                               if p.activation is not None else None))

    if p.mode == "masked":
        return _jnp(ReasonCode.SRSTE_TRAINING)
    if backend == "jnp":
        return _jnp(ReasonCode.BACKEND_JNP)
    if p.differentiating:
        return _jnp(ReasonCode.AUTODIFF)
    if shard is not None and all(s == 1 for s in shard.shards):
        shard = None  # trivial slicing: single-device execution class
    if p.sharded and shard is None:
        return _jnp(ReasonCode.NO_SHARD_SPEC)
    if p.b == 0:
        return _jnp(ReasonCode.EMPTY_BATCH)

    shards = (1, 1, 1)
    placement, local, collective = "single", None, None
    if shard is not None:
        shards = shard.shards
        local = registry.local_dims((p.b, p.ke, p.o), shards)
        if local is None:
            return _jnp(ReasonCode.SHARD_INDIVISIBLE, shards=shards,
                        b=p.b, ke=p.ke, o=p.o)
        if not _meta_axis_sliceable(p.mode, p.ke, p.n, p.m, shards[1]):
            return _jnp(ReasonCode.META_AXIS_SPLIT, n=p.n, m=p.m,
                        ke=p.ke, ske=shards[1])
        placement, collective = "shard_map", shard.collective

    sel = registry.select(p.mode, b=p.b, ke=p.ke, o=p.o, n=p.n, m=p.m,
                          dtype=p.dtype, backend=backend, shards=shards)
    if sel is None:
        where = "local shard " if shard is not None else ""
        dims = local if shard is not None else (p.b, p.ke, p.o)
        return _jnp(ReasonCode.NO_KERNEL_FITS, where=where,
                    b=dims[0], ke=dims[1], o=dims[2],
                    n=p.n, m=p.m, dtype=dt_name)
    entry, blocks = sel
    acts = (("static" if p.static_scales else "dynamic")
            if entry.quantized else None)
    # epilogue fusion: single placement only (shard_map bodies psum
    # BEFORE the epilogue may run); dual plans additionally need an
    # entry carrying a run_dual kernel
    epi_code = None
    if p.epilogue is not None:
        if placement != "single":
            epi_code = ReasonCode.EPILOGUE_SHARDED
        elif p.dual and entry.run_dual is None:
            epi_code = ReasonCode.EPILOGUE_NO_DUAL_KERNEL
        else:
            epi_code = ReasonCode.EPILOGUE_FUSED
    fused = epi_code is ReasonCode.EPILOGUE_FUSED
    # in-kernel dead-block skip: single placement only (shard_map bodies
    # would need per-shard maps), never on duals (no masked dual
    # kernels), and only on entries whose adapter carries the variant
    act_code = None
    if p.activation is not None:
        if placement != "single":
            act_code = ReasonCode.ACT_MASK_ONLY_SHARDED
        elif p.dual:
            act_code = ReasonCode.ACT_MASK_ONLY_DUAL
        elif not entry.activation_skip:
            act_code = ReasonCode.ACT_MASK_ONLY_ENTRY
        else:
            act_code = ReasonCode.ACT_SKIP
    skip = act_code is ReasonCode.ACT_SKIP
    # the nm_spmm family's M:1 mux follows n (slab when n divides 4)
    mux = None
    if p.mode == "compressed":
        from repro.kernels.nm_spmm.kernel import mux_form
        mux = mux_form(p.n)

    def _decision(blocks, code, source):
        return DispatchDecision(
            p.mode, backend, entry.name, blocks, reasons.render(code),
            blocks_source=source,
            placement=placement, local_dims=local, shards=shards if shard else None,
            collective=collective, act_scales=acts, dtype=dt_name,
            epilogue=p.epilogue, epilogue_fused=fused,
            activation=p.activation, activation_skip=skip,
            reason_code=code, epilogue_reason=epi_code,
            activation_reason=act_code, mux=mux)

    if dcfg.blocks is not None:
        return _decision(tuple(dcfg.blocks), ReasonCode.BLOCKS_PINNED,
                         "pinned")
    # autotune cache keys are per-shard local problems under shard_map —
    # that is the shape the kernel body actually runs
    kb, kke, ko = local if local is not None else (p.b, p.ke, p.o)
    key = _cache_key(entry.name, p, (kb, kke, ko), fused, skip)
    tuned = autotune.lookup(backend, key)
    if tuned is not None:
        return _decision(tuned, ReasonCode.BLOCKS_TUNED, "tuned")
    return _decision(blocks, ReasonCode.BLOCKS_FITTED, "fitted")


def plan_for(
    params: Dict[str, Any], x_shape: Sequence[int], cfg, dtype=jnp.float32,
    dispatch: Optional[DispatchConfig] = None,
    shard: Optional[ShardSpec] = None,
) -> DispatchDecision:
    """Planning convenience for launchers/benchmarks: no execution."""
    mode = _mode_of(params, cfg)
    b = math.prod(x_shape[:-1]) if len(x_shape) > 1 else 1
    fake_x = jax.ShapeDtypeStruct(tuple(x_shape), dtype)
    ke, o = _problem_dims(mode, params, fake_x)
    return plan(GemmProblem(mode, b=b, ke=ke, o=o, n=cfg.n, m=cfg.m,
                            dtype=dtype, sharded=_mesh_active(),
                            shard=shard,
                            static_scales=quant.has_static_scales(params)),
                dispatch=dispatch)


def _first_layer_slice(v, nd: int):
    """Strip leading layer-stack dims off one leaf (first layer's slice).

    Works on concrete arrays AND on ``jax.ShapeDtypeStruct`` leaves —
    the static plan auditor walks ``jax.eval_shape`` trees through the
    same :func:`iter_linear_items`, so weight-free traversal must not
    require a materialized array.
    """
    if v.ndim <= nd:
        return v
    if isinstance(v, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(tuple(v.shape[v.ndim - nd:]), v.dtype)
    return v.reshape((-1,) + tuple(v.shape[v.ndim - nd:]))[0]


def iter_linear_items(tree, _names=()):
    """Yield ``(names, leaf)`` for every SparseLinear param dict in a
    (possibly layer-stacked) params tree, with leading stack dims stripped
    (first layer's slice).  ``names`` is the dict-key path down to the
    leaf — launchers use it to recover the use-site parallelism hint
    (wq/w_in/... are column-parallel, wo/w_out row-parallel).  Linears
    sitting next to a ``router`` key are MoE expert stacks; their paths
    get an ``experts`` marker so ``gather_hint`` knows they are invoked
    hint-less inside the MoE's own shard_map body.

    This is the ONE place that knows how to recognize a linear layout
    inside a model pytree — pretune, the serving dispatch report, and
    the static plan auditor (which walks ``jax.eval_shape`` trees of
    ``ShapeDtypeStruct`` leaves) all build on it so the detection can't
    drift between them.
    """
    if isinstance(tree, dict):
        if quant.is_linear_leaf(tree):
            leaf = {}
            for k, v in tree.items():
                # static activation scales and calibration tags are 0-D
                # per layer; per-channel quantization scales and gather
                # indices are 1-D; everything else is a 2-D operand
                nd = (0 if k in (quant.ACT_SCALE_KEY, quant._CALIB_KEY)
                      else 1 if k in ("gather_idx", quant.SCALE_KEY)
                      else 2)
                leaf[k] = _first_layer_slice(v, nd)
            yield _names, leaf
            return
        mark = ("experts",) if "router" in tree else ()
        for k, v in tree.items():
            yield from iter_linear_items(v, _names + mark + (str(k),))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from iter_linear_items(v, _names + (f"[{i}]",))


def iter_linear_leaves(tree):
    """Back-compat wrapper over :func:`iter_linear_items` (leaves only)."""
    for _, leaf in iter_linear_items(tree):
        yield leaf


def leaf_config(names: Sequence[str], cfg):
    """Effective SparsityConfig for one yielded linear leaf.

    Rowwise layouts nest per-tier compressed segments under
    ``.../rowwise/n<N>``; the segment's own N (and mode "compressed")
    overrides the model-wide config for planning/tuning that leaf.
    """
    names = tuple(names)
    if len(names) >= 2 and names[-2] == "rowwise":
        tier = names[-1]
        if tier.startswith("n") and tier[1:].isdigit():
            return dataclasses.replace(cfg, n=int(tier[1:]),
                                       mode="compressed")
    return cfg


def leaf_shard_spec(names: Sequence[str], cfg) -> Optional[ShardSpec]:
    """Use-site ShardSpec for one yielded linear leaf — mirrors
    ``apply_linear`` exactly: unhinted sites (MoE experts, plain linears)
    get NO spec (they run the jnp fallback under a mesh); rowwise tier
    segments under a column hint keep only batch sharding (the channel
    permutation is global, so the out dim can't be pushed into tiers)."""
    from repro.core.sparse_linear import gather_hint

    hint = gather_hint(names)
    if hint is None:
        return None
    if hint == "col" and leaf_config(names, cfg) is not cfg:
        return shard_spec_from_env(None)
    return shard_spec_from_env(hint)


def pretune(params_tree, batch: int, cfg,
            dispatch: Optional[DispatchConfig] = None) -> int:
    """Eagerly autotune every linear in a (possibly layer-stacked) params
    tree.

    Serving loops are jitted, so ``sparse_matmul`` only ever sees tracers
    there and the concrete-only tuning path never fires; this walks the
    tree once OUTSIDE jit, runs each distinct kernel-eligible problem on
    a dummy batch, and fills the autotune cache before the loop traces.
    Under a mesh env each problem is tuned through its shard_map wrapper
    (per-shard local shapes — the blocks that will actually run).
    Returns the number of problems actually tuned (already-cached,
    jnp-routed, and unfittable problems don't count).
    """
    from repro.core.sparse_linear import gather_hint

    dcfg = dataclasses.replace(dispatch or _DEFAULT, autotune=True)
    seen = set()
    count = 0
    for names, leaf in iter_linear_items(params_tree):
        lcfg = leaf_config(names, cfg)
        try:
            ke = input_features(leaf, lcfg)
        except ValueError:
            continue
        hint = gather_hint(names)
        dt = leaf.get("values", leaf.get("w")).dtype
        # the storage dtype is part of the problem identity: an int8 and
        # an fp8 twin of the same shapes are DIFFERENT tuning problems
        sig = (hint, lcfg.n, lcfg.m, dtype_name(dt)) + tuple(
            sorted((k, tuple(v.shape)) for k, v in leaf.items()))
        if sig in seen:
            continue
        seen.add(sig)
        # quantized leaves plan on their storage dtype (int8 | fp8) but
        # consume float activations (the engine row-quantizes them)
        x = jnp.zeros((batch, ke),
                      jnp.float32 if quant.is_quantized_dtype(dt) else dt)
        mode = _mode_of(leaf, lcfg)
        _, o = _problem_dims(mode, leaf, x)
        shard = leaf_shard_spec(names, cfg)
        decision = plan(
            GemmProblem(mode, b=batch, ke=ke, o=o, n=lcfg.n, m=lcfg.m,
                        dtype=dt, sharded=_mesh_active(), shard=shard,
                        static_scales=quant.has_static_scales(leaf)),
            dispatch=dcfg)
        if not decision.uses_kernel or decision.blocks_source != "fitted":
            continue  # jnp-routed or already cached: nothing to tune
        sparse_matmul(x, leaf, lcfg, dispatch=dcfg, shard=shard)
        count += 1
    return count


def dispatch_report(params_tree, batches, cfg,
                    dispatch: Optional[DispatchConfig] = None) -> List[str]:
    """Distinct (shape -> engine decision) plan lines for a params tree.

    ``batches`` is the tuple of leading batch widths the serving path
    will actually run (e.g. ``(slots, prefill_chunk)`` — decode steps
    and prefill chunks can plan differently, and the report shows both).
    Shard-aware: under a mesh env each line carries global -> local
    shapes and the chosen collective.  Ends with the count of
    ``nm_spmm`` plan lines by mux form (``mux=slab`` / ``mux=rows``) and
    the autotune cache counters.  This is the engine-owned successor of
    the plan report ``launch/serve.py`` used to build privately; the
    launcher, the examples, and ``Prepared.dispatch_report`` all render
    these lines.
    """
    from repro.core.sparse_linear import gather_hint
    from . import autotune as kautotune

    if isinstance(batches, int):
        batches = (batches,)
    dcfg = dispatch or _DEFAULT
    seen = {}
    pairs = {}
    for batch in batches:
        for names, leaf in iter_linear_items(params_tree):
            lcfg = leaf_config(names, cfg)
            try:
                ke = input_features(leaf, lcfg)
            except ValueError:
                continue
            hint = gather_hint(names)
            shard = leaf_shard_spec(names, cfg)
            dt = leaf.get("values", leaf.get("w")).dtype
            d = plan_for(leaf, (batch, 1, ke), lcfg,
                         dtype=dt, dispatch=dcfg, shard=shard)
            o = leaf["w"].shape[-1] if "w" in leaf else leaf["values"].shape[-1]
            seen.setdefault((batch, d.mode, lcfg.n, ke, o, hint), d)
            # sibling w_gate/w_in leaves form a gate-up pair — collect
            # them to report the fused dual plan the models actually run
            if names and names[-1] in ("w_gate", "w_in"):
                pairs.setdefault((batch, tuple(names[:-1])),
                                 {})[names[-1]] = (names, leaf)
    dual_seen = {}
    for (batch, _parent), found in pairs.items():
        if "w_gate" not in found or "w_in" not in found:
            continue
        gnames, gleaf = found["w_gate"]
        _, uleaf = found["w_in"]
        lcfg = leaf_config(gnames, cfg)
        try:
            ke = input_features(gleaf, lcfg)
        except ValueError:
            continue
        hint = gather_hint(gnames)
        shard = leaf_shard_spec(gnames, cfg)
        dt = gleaf.get("values", gleaf.get("w")).dtype
        fake_x = jax.ShapeDtypeStruct((batch, ke), jnp.float32)
        mode = _mode_of(gleaf, lcfg)
        _, o = _problem_dims(mode, gleaf, fake_x)
        if (_mode_of(uleaf, lcfg) != mode
                or _problem_dims(mode, uleaf, fake_x) != (ke, o)):
            continue
        d = plan(GemmProblem(mode, b=batch, ke=ke, o=o, n=lcfg.n, m=lcfg.m,
                             dtype=dt, sharded=_mesh_active(), shard=shard,
                             static_scales=quant.has_static_scales(gleaf),
                             epilogue="silu_mul", dual=True),
                 dispatch=dcfg)
        dual_seen.setdefault((batch, d.mode, lcfg.n, ke, o, hint), d)
    lines = []
    for (batch, _, n, ke, o, hint), d in sorted(seen.items(), key=lambda kv: (
            kv[0][0], kv[0][1], kv[0][2], kv[0][3], kv[0][4],
            str(kv[0][5]))):
        loc = ""
        if d.uses_shard_map:
            lb, lke, lo = d.local_dims
            loc = f" -> local (B={lb}, K={lke}, O={lo})"
        lines.append(f"  [{hint or 'rep'}] {n}:{cfg.m} "
                     f"global (B={batch}, K={ke}, O={o})"
                     f"{loc} {describe(d)}")
    for (batch, _, n, ke, o, hint), d in sorted(
            dual_seen.items(), key=lambda kv: (
                kv[0][0], kv[0][1], kv[0][2], kv[0][3], kv[0][4],
                str(kv[0][5]))):
        lines.append(f"  [gate-up {hint or 'rep'}] {n}:{cfg.m} "
                     f"global (B={batch}, K={ke}, O={o}) {describe(d)}")
    # how often the slab mux engages: nm_spmm plan lines by mux form
    forms = [d.mux for d in (*seen.values(), *dual_seen.values())
             if d.uses_kernel and d.mux is not None]
    if forms:
        lines.append(f"  nm_spmm mux: {forms.count('slab')} slab / "
                     f"{forms.count('rows')} rows site(s)")
    st = kautotune.stats()
    lines.append(f"  autotune cache: {st['hits']} hit(s) / "
                 f"{st['misses']} miss(es)")
    return lines


def _entry_by_name(mode: str, name: str) -> KernelEntry:
    for e in registry.entries(mode):
        if e.name == name:
            return e
    raise KeyError(f"kernel {name!r} not registered for mode {mode!r}")


def _shard_param_specs(
    mode: str, shard: ShardSpec, params: Dict[str, Any],
) -> Dict[str, P]:
    """Per-leaf PartitionSpecs for one SparseLinear layout under a shard
    spec.  The compressed values/meta share the contraction slicing (their
    row axes are K_c and K_c/4 — same mesh axes, scaled dims); gather_idx
    rides the contraction axis and replicates otherwise.  Quantized
    layouts carry extra leaves: the per-channel weight ``scale`` (O,)
    shards on the out-dim axes (derived from the same use-site spec as the
    operand it scales), and the scalar ``act_scale`` replicates.
    """
    ke, o = shard.ke, shard.o

    def spec_for(key: str) -> P:
        if key in ("w", "values", "meta_packed"):
            return P(ke, o)
        if key == "gather_idx":
            return P(ke)
        if key == quant.SCALE_KEY:
            return P(o)
        return P()   # act_scale and any other scalar-ish aux leaf
    if mode not in ("dense", "masked", "compressed", "gather"):
        raise ValueError(f"no shard specs for mode {mode!r}")
    return {k: spec_for(k) for k in params}


def _shard_map_runner(
    entry: KernelEntry, mode: str, cfg, shard: ShardSpec,
    blocks: Blocks, interpret: bool, out_dtype, params: Dict[str, Any],
) -> Callable[[jax.Array, Dict[str, Any]], jax.Array]:
    """Wrap ``entry.run`` in shard_map with the use-site specs.

    Each shard runs the Pallas kernel on its local (b, ke, o) tile; a
    sharded contraction dim leaves partial products that are combined
    with ``psum`` over those axes — the out-dim-sharded case needs no
    collective, the output simply stays sharded on the model axis.

    Quantized entries (int8 and fp8 alike) keep their ordering contract
    under a sharded contraction: activations quantize per-row INSIDE the
    shard body (the local absmax is lifted to the row's global absmax
    with a ``pmax`` over the contraction axes so every shard shares one
    scale; calibrated static scales are coherent by construction), each
    shard contracts narrow x narrow into **raw accumulator partials**
    (int32 for int8 — exact; fp32 for fp8), the partials are psum'd in
    the accumulator dtype, and the gathered result is dequantized once.
    Float entries psum fp32 partials before the output cast, as before.
    """
    from jax import shard_map

    x_spec = P(shard.batch, shard.ke)
    p_specs = _shard_param_specs(mode, shard, params)
    out_spec = P(shard.batch, shard.o)
    needs_psum = shard.collective == "psum"
    quantized_psum = needs_psum and entry.run_quantized is not None
    qdt = quant.quant_dtype(params)

    def body(x_l, params_l):
        if quantized_psum:
            b_l = x_l.shape[0]
            if quant.ACT_SCALE_KEY in params_l:
                xq, xs = quant.quantize_rows_static(
                    x_l, params_l[quant.ACT_SCALE_KEY], qdt)
            else:
                # per-row absmax of the LOCAL slice, lifted to the global
                # row absmax so the raw partials share one scale
                absmax = jnp.max(jnp.abs(x_l.astype(jnp.float32)),
                                 axis=-1, keepdims=True)
                xq, xs = quant.quantize_rows(
                    x_l, absmax=jax.lax.pmax(absmax, shard.ke), dtype=qdt)
            xq_p, _ = _pad_rows(xq, xs, _q_padded_b(b_l))
            acc = entry.run_quantized(xq_p, params_l, cfg, blocks, interpret)
            acc = jax.lax.psum(acc, shard.ke)
            ws = params_l[quant.SCALE_KEY].reshape(1, -1)
            y = acc[:b_l].astype(jnp.float32) * xs * ws
            return y.astype(out_dtype)
        y = entry.run(x_l, params_l, cfg, lambda w: w, blocks, interpret,
                      jnp.float32 if needs_psum else out_dtype)
        if needs_psum:
            y = jax.lax.psum(y, shard.ke)
        return y.astype(out_dtype)

    return shard_map(body, mesh=shard.mesh, in_specs=(x_spec, p_specs),
                     out_specs=out_spec, check_vma=False)


def sparse_matmul(
    x: jax.Array,
    params: Dict[str, Any],
    cfg,
    *,
    constrain_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
    dispatch: Optional[DispatchConfig] = None,
    shard: Optional[ShardSpec] = None,
    epilogue: Optional[Epilogue] = None,
    activation: Optional[ActivationSpec] = None,
    local: bool = False,
) -> jax.Array:
    """y = x @ W for any SparseLinear layout, via the dispatch engine.

    ``x``: (..., K_eff) activations; ``params``: one of the SparseLinear
    layouts (``w`` | ``values``+``meta_packed`` | ``values``+``gather_idx``);
    ``cfg``: a SparsityConfig-like object (``.mode .n .m .is_sparse
    .srste_lam``).  ``constrain_fn`` is applied to the weight operand in
    the single-device kernel and reference paths (sharding-constraint
    preservation); under shard_map the in/out specs own the layout.
    ``shard`` routes the kernel through the mesh-aware shard_map class.

    ``epilogue`` is a post-GEMM lattice point (dequantize -> bias ->
    activation -> requantize; see ``repro.kernels.epilogue``).  On a
    single-placement kernel decision it is applied IN the pallas_call,
    on the fp32 accumulator tile in VMEM before the one HBM write-back;
    every other route (jnp reference, shard_map, grad) computes the
    same point unfused with ``apply_reference`` — which skips the
    requantize, so a fallback never changes end-to-end numerics.

    ``x`` may arrive already narrow (int8/fp8): that means an upstream
    kernel's fused epilogue requantized it against THIS leaf's
    calibrated ``act_scale``, and the quantize pass here is skipped.

    ``activation`` opts this call into the dynamic activation-sparsity
    execution class: the induced mask is applied to ``x`` up front on
    EVERY route (identity for kind ``"zeros"``), and when the plan lands
    on a single-placement kernel whose adapter carries a masked variant,
    dead (row-block, K-block) tiles are additionally skipped in-kernel —
    loads elided, dots never issued — with bit-identical output.

    ``local=True`` says this call already runs INSIDE a shard_map body
    (e.g. MoE expert linears): planning must not consult the mesh env,
    because nesting shard_map is not supported.
    """
    dcfg = dispatch or _DEFAULT
    g = constrain_fn or (lambda w: w)
    mode = _mode_of(params, cfg)
    if activation is not None:
        x = apply_mask(x, activation)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    b = x2.shape[0]
    ke, o = _problem_dims(mode, params, x2)
    # the dtype axis the engine plans on: the storage dtype (int8 | fp8)
    # for quantized layouts — the weight operand drives kernel selection
    # — else the activation dtype as before
    exec_dtype = quant.quant_dtype(params) or x2.dtype

    if epilogue is not None and epilogue.spec.is_identity:
        epilogue = None
    if epilogue is not None and epilogue.spec.act == "silu_mul":
        raise ValueError("silu_mul is the dual gate-up lattice point — "
                         "route it through gate_up_matmul")

    pre_q = quant.is_quantized_dtype(x2.dtype)
    if pre_q and jnp.dtype(x2.dtype) != jnp.dtype(exec_dtype):
        raise ValueError(
            f"pre-quantized activations ({dtype_name(x2.dtype)}) do not "
            f"match this leaf's storage dtype ({dtype_name(exec_dtype)})")

    # static-scale calibration: report this site's activation absmax
    # through the engine hook (no-op outside a calibration context;
    # narrow activations can't occur during calibration — the fused
    # requant needs the static scales calibration is producing)
    if (quant.calibration_active() and quant._CALIB_KEY in params
            and not pre_q):
        quant.record_calibration(params[quant._CALIB_KEY], x2)

    problem = GemmProblem(
        mode, b=b, ke=ke, o=o, n=cfg.n, m=cfg.m, dtype=exec_dtype,
        sharded=False if local else _mesh_active(),
        shard=shard,
        static_scales=quant.has_static_scales(params),
        epilogue=epilogue.spec.point if epilogue is not None else None,
        activation=activation.point if activation is not None else None,
    )
    decision = plan(problem, dispatch=dcfg)

    if pre_q and not (decision.uses_kernel
                      and decision.placement == "single"):
        # fallback tiers contract float activations: undo the upstream
        # fused requantize with the leaf's own static scale
        s = jnp.asarray(params[quant.ACT_SCALE_KEY],
                        jnp.float32).reshape(())
        x2 = x2.astype(jnp.float32) * s

    if not decision.uses_kernel:
        y2 = _JNP_IMPL[mode](x2, params, cfg, g)
        if epilogue is not None:
            y2 = epilib.apply_reference(y2, epilogue)
        return y2.reshape(*lead, o)

    entry = _entry_by_name(mode, decision.kernel)
    interpret = decision.backend == "interpret"
    blocks = decision.blocks
    out_dt = jnp.float32 if pre_q else x2.dtype
    epi_ops = ((epilogue.bias, epilogue.requant_scale)
               if epilogue is not None else None)

    def _with_epilogue(ops):
        return None if ops is None else epilib.Epilogue(epilogue.spec, *ops)

    def reference(x2_, params_, ops):
        y = _JNP_IMPL[mode](x2_, params_, cfg, g)
        ep = _with_epilogue(ops)
        return y if ep is None else epilib.apply_reference(y, ep)

    if decision.uses_shard_map:
        lb, lke, lo = decision.local_dims
        runner = lambda blk: _shard_map_runner(
            entry, mode, cfg, shard, blk, interpret, out_dt,
            params)(x2, params)
        # Autotune the per-shard local problem through the same wrapper.
        if (dcfg.autotune and decision.blocks_source == "fitted"
                and not isinstance(x2, jax.core.Tracer)):
            key = _cache_key(entry.name, problem, (lb, lke, lo),
                             False, False)
            cands = entry.candidates(lb, lke, lo, cfg.n, cfg.m, exec_dtype)
            tuned = autotune.tune(runner, cands, backend=decision.backend,
                                  key=key, persist=dcfg.persist_autotune)
            if tuned is not None:
                blocks = tuned
        def run_sharded(x2_, params_, ops):
            y = _shard_map_runner(entry, mode, cfg, shard, blocks, interpret,
                                  out_dt, params_)(x2_, params_)
            ep = _with_epilogue(ops)  # psum happened inside: apply unfused
            return y if ep is None else epilib.apply_reference(y, ep)

        y2 = _reference_vjp(run_sharded, reference, x2, params, epi_ops)
        return y2.reshape(*lead, o)

    fused_epi = epilogue if decision.epilogue_fused else None
    # the masked (block-skip) variant only runs when the plan granted it
    # — the adapter then derives the skip maps from the operand it
    # actually contracts (padded narrow rows for the quantized entries)
    act_kw = ({"activation": activation}
              if decision.activation_skip else {})

    # Autotune on first concrete sighting of a problem (never mid-trace).
    if (dcfg.autotune and decision.blocks_source == "fitted"
            and not isinstance(x2, jax.core.Tracer)):
        key = _cache_key(entry.name, problem, (b, ke, o),
                         fused_epi is not None, decision.activation_skip)
        cands = entry.candidates(b, ke, o, cfg.n, cfg.m, exec_dtype)
        tuned = autotune.tune(
            lambda blk: entry.run(x2, params, cfg, g, blk, interpret,
                                  out_dt, epilogue=fused_epi, **act_kw),
            cands, backend=decision.backend, key=key,
            persist=dcfg.persist_autotune,
        )
        if tuned is not None:
            blocks = tuned

    def run_single(x2_, params_, ops):
        ep = _with_epilogue(ops)
        fused = ep if decision.epilogue_fused else None
        y = entry.run(x2_, params_, cfg, g, blocks, interpret, out_dt,
                      epilogue=fused, **act_kw)
        if ep is not None and fused is None:
            y = epilib.apply_reference(y, ep)
        return y

    y2 = _reference_vjp(run_single, reference, x2, params, epi_ops)
    return y2.reshape(*lead, o)


def requant_decision(
    consumer_params: Dict[str, Any], batch_shape: Sequence[int], cfg,
    dispatch: Optional[DispatchConfig] = None,
    shard: Optional[ShardSpec] = None,
) -> Tuple[Optional[Tuple[str, jax.Array]], ReasonCode]:
    """Should the PRODUCER of these activations fuse a requantize — and
    if not, the structured :class:`ReasonCode` saying why.

    A producing kernel may extend its epilogue with
    ``requant:<dtype>`` — emitting the narrow rows the next quantized
    linear contracts directly — exactly when the CONSUMER leaf will (a)
    quantize against a calibrated static ``act_scale`` (the fused cast
    must hit the same scale the consumer's own quantize pass would) and
    (b) run a single-placement kernel itself (the jnp dequantize
    reference and the shard_map bodies want float rows).
    ``batch_shape`` is the leading (batch) shape of the activations the
    producer will emit.  Returns ``((dtype_name, scalar_scale), code)``
    on a fused plan or ``(None, code)`` on a decline — both sides derive
    the decision from this one function, so producer and consumer can
    never disagree, and the plan auditor lints the decline codes.
    """
    qdt = quant.quant_dtype(consumer_params)
    if qdt is None:
        # a rowwise consumer hides its quantized operands in per-tier
        # segments — the wrapper itself plans nothing, so the producer
        # cannot target one scale; that is a LAYOUT decline (the lint
        # gate warns), not a benign float consumer
        if isinstance(consumer_params, dict) and "rowwise" in consumer_params \
                and any(quant.quant_dtype(t) is not None
                        for t in consumer_params["rowwise"].values()):
            return None, ReasonCode.REQUANT_LAYOUT
        return None, ReasonCode.REQUANT_NO_QUANT
    if not quant.has_static_scales(consumer_params):
        return None, ReasonCode.REQUANT_DYNAMIC_SCALES
    try:
        ke = input_features(consumer_params, cfg)
        d = plan_for(consumer_params, tuple(batch_shape) + (ke,), cfg,
                     dtype=qdt, dispatch=dispatch, shard=shard)
    except ValueError:   # unrecognized layout (e.g. rowwise): no requant
        return None, ReasonCode.REQUANT_LAYOUT
    if not (d.uses_kernel and d.placement == "single"):
        return None, ReasonCode.REQUANT_CONSUMER_FALLBACK
    s = jnp.asarray(consumer_params[quant.ACT_SCALE_KEY],
                    jnp.float32).reshape(())
    return (dtype_name(qdt), s), ReasonCode.REQUANT_FUSED


def requant_plan(
    consumer_params: Dict[str, Any], batch_shape: Sequence[int], cfg,
    dispatch: Optional[DispatchConfig] = None,
    shard: Optional[ShardSpec] = None,
) -> Optional[Tuple[str, jax.Array]]:
    """:func:`requant_decision` minus the reason code — the execution
    paths (``apply_mlp``, the MoE expert FFN) only need the operands."""
    result, _ = requant_decision(consumer_params, batch_shape, cfg,
                                 dispatch=dispatch, shard=shard)
    return result


def _concat_gate_up(pg, pu, mode):
    """One concatenated-O layout for an eligible gate-up pair, so the
    UNFUSED fallback still reads the activation once (one GEMM over
    ``[Wg | Wu]`` instead of two over the same x).  ``None`` when the
    leaves cannot concat — gather keeps per-site index streams, and
    mismatched aux leaves would change quantization semantics."""
    if (quant.SCALE_KEY in pg) != (quant.SCALE_KEY in pu):
        return None
    if (quant.ACT_SCALE_KEY in pg) != (quant.ACT_SCALE_KEY in pu):
        return None
    cat = {}
    if mode == "dense":
        cat["w"] = jnp.concatenate([pg["w"], pu["w"]], axis=1)
    elif mode == "compressed":
        if pg["meta_packed"].shape != pu["meta_packed"].shape:
            return None
        cat["values"] = jnp.concatenate([pg["values"], pu["values"]],
                                        axis=1)
        cat["meta_packed"] = jnp.concatenate(
            [pg["meta_packed"], pu["meta_packed"]], axis=1)
    else:
        return None
    if quant.SCALE_KEY in pg:
        cat[quant.SCALE_KEY] = jnp.concatenate(
            [pg[quant.SCALE_KEY].reshape(-1),
             pu[quant.SCALE_KEY].reshape(-1)], axis=0)
    if quant.ACT_SCALE_KEY in pg:
        # both sites calibrated on the SAME tensor, so their scales
        # agree; the gate leaf's scalar quantizes the shared rows
        cat[quant.ACT_SCALE_KEY] = pg[quant.ACT_SCALE_KEY]
    return cat   # note: no _CALIB_KEY — gate_up_matmul records per-site


def gate_up_matmul(
    x: jax.Array,
    params_g: Dict[str, Any],
    params_u: Dict[str, Any],
    cfg,
    *,
    constrain_fn: Optional[Callable[[jax.Array], jax.Array]] = None,
    dispatch: Optional[DispatchConfig] = None,
    shard: Optional[ShardSpec] = None,
    epilogue: Optional[Epilogue] = None,
    activation: Optional[ActivationSpec] = None,
    local: bool = False,
) -> jax.Array:
    """``silu(x @ Wg) * (x @ Wu)`` — the gate-up projection as ONE
    engine call.

    ``epilogue`` is the SAME :class:`Epilogue` object ``sparse_matmul``
    takes — the gate-up path no longer smuggles a ``requant=`` /
    ``requant_scale=`` side-channel.  It must sit on the ``silu_mul``
    lattice point (optionally extended with ``requant:<dtype>`` from
    :func:`requant_plan` on the next linear); ``None`` means the bare
    ``silu_mul`` point.  ``activation`` / ``local`` thread the dynamic
    activation-sparsity class and the inside-shard_map marker exactly as
    on :func:`sparse_matmul`.

    When both leaves share mode/shape/dtype class and the plan lands on
    a single-placement kernel with a ``run_dual`` variant, ONE
    pallas_call reads each activation tile once, contracts it against
    both weights, and emits the epilogue directly.  Otherwise the
    fallback still reads the activation once where that helps — dense
    and compressed pairs headed for a (non-dual) kernel concat along O
    into a single GEMM, while jnp-tier pairs run as two plain GEMMs
    (a per-call weight concat costs more than a decode-shape GEMM
    there) — and applies the float silu*mul reference (never the
    requant: the consumer's own quantize pass is bit-identical on
    float rows, and the caller sees that in the float dtype of the
    result).
    """
    dcfg = dispatch or _DEFAULT
    g = constrain_fn or (lambda w: w)
    if epilogue is None:
        epilogue = epilib.make(act="silu_mul")
    if epilogue.spec.act != "silu_mul" or epilogue.spec.bias:
        raise ValueError(
            f"gate_up_matmul epilogue must sit on the silu_mul lattice "
            f"point (optionally +requant), got {epilogue.spec.point!r}")
    mode_g = _mode_of(params_g, cfg)
    mode_u = _mode_of(params_u, cfg)
    if activation is not None:
        x = apply_mask(x, activation)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    b = x2.shape[0]
    ke, o = _problem_dims(mode_g, params_g, x2)

    # both sites see the same activations: record each calibration tag
    # here (the concat fallback cannot carry two tags through one leaf)
    if quant.calibration_active():
        for p in (params_g, params_u):
            if quant._CALIB_KEY in p:
                quant.record_calibration(p[quant._CALIB_KEY], x2)

    qdt = quant.quant_dtype(params_g)
    pair_ok = (
        mode_g == mode_u
        and mode_g in ("dense", "compressed", "gather")
        and _problem_dims(mode_u, params_u, x2) == (ke, o)
        and quant.quant_dtype(params_u) == qdt
        and (quant.has_static_scales(params_u)
             == quant.has_static_scales(params_g))
    )
    spec, epi = epilogue.spec, epilogue

    decision = None
    if pair_ok:
        decision = plan(
            GemmProblem(
                mode_g, b=b, ke=ke, o=o, n=cfg.n, m=cfg.m,
                dtype=qdt or x2.dtype,
                sharded=False if local else _mesh_active(), shard=shard,
                static_scales=quant.has_static_scales(params_g),
                epilogue=spec.point, dual=True,
                activation=(activation.point if activation is not None
                            else None)),
            dispatch=dcfg)
    if decision is not None and decision.epilogue_fused:
        entry = _entry_by_name(mode_g, decision.kernel)
        interpret = decision.backend == "interpret"
        pre_q = quant.is_quantized_dtype(x2.dtype)
        out_dt = jnp.float32 if pre_q else x2.dtype

        def run_dual(x2_, pg, pu, rq_scale):
            return entry.run_dual(
                x2_, pg, pu, cfg, g, decision.blocks, interpret, out_dt,
                epilogue=epilib.Epilogue(spec, requant_scale=rq_scale))

        def reference_dual(x2_, pg, pu, rq_scale):
            y_g = _JNP_IMPL[mode_g](x2_, pg, cfg, g)
            y_u = _JNP_IMPL[mode_g](x2_, pu, cfg, g)
            h = jax.nn.silu(y_g.astype(jnp.float32)) * y_u.astype(jnp.float32)
            return h.astype(out_dt)

        y2 = _reference_vjp(run_dual, reference_dual, x2, params_g, params_u,
                            epi.requant_scale)
        return y2.reshape(*lead, o)

    # the concat collapse (one GEMM over 2o, activation read once from
    # HBM) only pays for itself when a kernel actually runs it; on the
    # jnp tier the per-call O(ke*2o) weight concat costs more than the
    # decode-shape GEMM it feeds, so two plain XLA GEMMs win there.
    # Under shard_map both weights are split along o over the model
    # axis, and [Wg | Wu] split the same way holds other columns than
    # either: every call would move the weights between chips (an
    # all-to-all per weight, then collective-permutes to slice the
    # result apart), so sharded pairs run as two shard_map GEMMs
    cat = (_concat_gate_up(params_g, params_u, mode_g)
           if pair_ok and decision is not None and decision.uses_kernel
           and not decision.uses_shard_map
           else None)
    if cat is not None:
        y2 = sparse_matmul(x2, cat, cfg, constrain_fn=g, dispatch=dcfg,
                           shard=shard, activation=activation, local=local)
        y_g, y_u = y2[:, :o], y2[:, o:]
    else:
        y_g = sparse_matmul(x2, params_g, cfg, constrain_fn=g,
                            dispatch=dcfg, shard=shard,
                            activation=activation, local=local)
        y_u = sparse_matmul(x2, params_u, cfg, constrain_fn=g,
                            dispatch=dcfg, shard=shard,
                            activation=activation, local=local)
    h = jax.nn.silu(y_g.astype(jnp.float32)) * y_u.astype(jnp.float32)
    return h.astype(y_g.dtype).reshape(*lead, o)


def attention(
    qg: jax.Array,           # (B, Hkv, G, Tq, D) grouped queries
    k: jax.Array,            # (B, Tk, Hkv, D)
    v: jax.Array,            # (B, Tk, Hkv, D)
    *,
    causal: bool,
    chunk: int,
    q_offset: int = 0,
    p_bf16: bool = False,
    s_bf16: bool = False,
    dispatch: Optional[DispatchConfig] = None,
) -> jax.Array:
    """Full-sequence attention via the dispatch engine.

    On a kernel backend the registry's ``flash_attention`` Pallas entry
    runs (self-attention shapes only: Tq == Tk, no query offset); the jnp
    chunked online-softmax formulation with its recompute-from-LSE custom
    VJP remains the reference, the backward pass of the kernel call, and
    the fallback — under a mesh env (attention sharding is head-parallel
    and XLA already keeps it collective-free), or when a shape fails the
    tiling constraints.
    """
    from repro.models.attention import chunked_attention  # local: avoid cycle

    dcfg = dispatch or _DEFAULT
    b, hkv, grp, tq, d = qg.shape
    tk = k.shape[1]
    decision = plan(
        GemmProblem("attention", b=tq, ke=tk, o=d, n=4, m=4,
                    dtype=qg.dtype, sharded=_mesh_active()),
        dispatch=dcfg,
    )
    if not decision.uses_kernel or tq != tk or q_offset != 0:
        return chunked_attention(qg, k, v, causal, chunk, q_offset,
                                 p_bf16, s_bf16)
    entry = _entry_by_name("attention", decision.kernel)
    interpret = decision.backend == "interpret"

    def run_flash(qg_, k_, v_):
        # (B, Hkv, G, T, D) -> (B, Hq, T, D); Hq = Hkv*G flattening matches
        # the wrapper's jnp.repeat KV-head expansion order
        out = entry.run(None, {"q": qg_.reshape(b, hkv * grp, tq, d),
                               "k": k_.transpose(0, 2, 1, 3),
                               "v": v_.transpose(0, 2, 1, 3)},
                        types.SimpleNamespace(causal=causal), None,
                        decision.blocks, interpret, qg.dtype)
        return out.reshape(b, hkv, grp, tq, d)

    def reference(qg_, k_, v_):
        return chunked_attention(qg_, k_, v_, causal, chunk, q_offset,
                                 p_bf16, s_bf16)

    return _reference_vjp(run_flash, reference, qg, k, v)
