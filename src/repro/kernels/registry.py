"""Kernel registry: the dispatch table behind the unified sparse-GEMM engine.

The paper's point is that ONE engine behind the GEMM ISA serves dense
(4:4), 2:4, 1:4 and row-wise/unstructured layers.  This module is that
table on the software side: every Pallas kernel registers a
:class:`KernelEntry` describing which execution mode it implements and
— via ``fit_blocks`` — which (shape, N:M, dtype) problems it can legally
tile.  ``select`` walks the entries in priority order and returns the
first (entry, blocks) that fits; a ``None`` result means "no kernel
applies, use the jnp reference formulation".

dtype is a real selection axis, not a cast: the int8 (VNNI-lineage) and
fp8 (e4m3fn) entries fit only problems whose quantized storage dtype
matches, and because the narrow dtypes pack 4x more values per 32-bit
lane register than fp32, their legal contraction blocks are multiples of
the 32-row sublane quantum (vs 8 for fp32) — the float entries decline
quantized problems rather than silently upcasting, and each quantized
class declines the other's.  An entry may additionally carry a
``supported(backend)`` predicate for constraints the (shape, dtype)
signature can't express — the fp8 entries use it to require a native
fp8 MXU dot on the ``tpu`` backend (see :func:`fp8_native_dot`) while
``interpret`` mode always emulates.

Backends
--------
``tpu``        compiled Mosaic execution (real TPU devices present)
``interpret``  the same kernel bodies emulated on CPU (tests / parity)
``jnp``        no kernel at all — the documented pure-jnp reference path
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax

__all__ = [
    "KernelEntry",
    "register",
    "entries",
    "select",
    "local_dims",
    "detect_backend",
    "resolve_backend",
    "largest_fitting_block",
    "dtype_name",
    "fp8_native_dot",
    "supports_fp8",
    "kernel_label",
    "KERNEL_BACKENDS",
]

Blocks = Tuple[int, int, int]  # (block_b, block_ke, block_o)

KERNEL_BACKENDS = ("tpu", "interpret")
_ENV_BACKEND = "REPRO_KERNEL_BACKEND"


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    """One kernel the engine can dispatch to.

    ``fit_blocks(b, ke, o, n, m, dtype) -> Blocks | None`` returns legal
    default block sizes for the problem, or ``None`` when the kernel's
    shape constraints cannot be met (the registry then falls through).
    ``candidates`` enumerates legal block choices for the autotuner.
    ``run(x2d, params, cfg, g, blocks, interpret, out_dtype, ...)``
    executes it; a GEMM entry's ``run`` also takes ``epilogue``,
    ``activation`` (the block-skip form), ``up`` (the up projection's
    leaf: ONE pallas_call contracting the activation tile against both
    weights and emitting ``silu(g) * u``) and, on quantized entries,
    ``raw`` (already-quantized activations in, the undequantized
    accumulator out, so a contraction-sharded problem can psum the raw
    partials and dequantize once on the gathered result).

    ``quantized`` marks the narrow-dtype entries (int8 VNNI lineage and
    fp8) — the engine uses it to annotate activation-scale handling and
    to route the sharded contraction class.

    ``supported(backend) -> bool``, when set, vetoes the entry on
    backends whose hardware can't execute it — constraints the
    (shape, dtype) signature handed to ``fit_blocks`` cannot express
    (e.g. the fp8 entries require a native fp8 MXU dot on ``tpu``).
    """

    name: str
    mode: str                      # dense | compressed | gather | attention
    fit_blocks: Callable[..., Optional[Blocks]]
    run: Callable[..., jax.Array]
    candidates: Callable[..., Sequence[Blocks]]
    priority: int = 0
    quantized: bool = False
    supported: Optional[Callable[[str], bool]] = None


_REGISTRY: Dict[str, List[KernelEntry]] = {}

# storage dtype of the weight values -> suffix of the quantized entries
_ENTRY_SUFFIX = {"int8": "_int8", "float8_e4m3fn": "_fp8"}


def kernel_label(name: str, family: str, values_dtype=None) -> dict:
    """``pallas_call`` keywords that name a kernel in HLO text and in
    profiler traces: ``name`` is the kernel's form (``nm_spmm_dual``),
    and ``metadata`` records the registry entry that runs it — ``family``
    on float weights, ``family_int8`` / ``family_fp8`` on quantized
    ones — as ``kernel_metadata={"kernel":"nm_spmm_int8"}``."""
    entry = family
    if values_dtype is not None:
        entry += _ENTRY_SUFFIX.get(jax.numpy.dtype(values_dtype).name, "")
    return {"name": name, "metadata": {"kernel": entry}}



def register(entry: KernelEntry) -> KernelEntry:
    """Add a kernel to the dispatch table (idempotent per name)."""
    lst = _REGISTRY.setdefault(entry.mode, [])
    lst[:] = [e for e in lst if e.name != entry.name]
    lst.append(entry)
    lst.sort(key=lambda e: -e.priority)
    return entry


def entries(mode: Optional[str] = None) -> List[KernelEntry]:
    if mode is None:
        return [e for lst in _REGISTRY.values() for e in lst]
    return list(_REGISTRY.get(mode, []))


def local_dims(
    dims: Sequence[int], shards: Sequence[int]
) -> Optional[Tuple[int, ...]]:
    """Per-shard problem dims, or ``None`` when a shard count doesn't
    evenly divide its dim (shard_map needs exact divisibility)."""
    out = []
    for d, s in zip(dims, shards):
        if s <= 0 or d % s != 0:
            return None
        out.append(d // s)
    return tuple(out)


def select(
    mode: str, *, b: int, ke: int, o: int, n: int, m: int, dtype,
    backend: str, shards: Tuple[int, int, int] = (1, 1, 1),
) -> Optional[Tuple[KernelEntry, Blocks]]:
    """Highest-priority kernel whose constraints fit, with its blocks.

    ``shards`` is the mesh slicing of (b, ke, o); blocks are fitted
    against the PER-SHARD local problem, which is what the kernel body
    actually sees under ``shard_map``.  Returns ``None`` when no
    registered kernel supports the (local) problem on the given backend —
    the caller must fall back to the jnp reference.
    """
    if backend not in KERNEL_BACKENDS:
        return None
    loc = local_dims((b, ke, o), shards)
    if loc is None:
        return None
    b, ke, o = loc
    for entry in _REGISTRY.get(mode, []):
        if entry.supported is not None and not entry.supported(backend):
            continue
        blocks = entry.fit_blocks(b, ke, o, n, m, dtype)
        if blocks is not None:
            return entry, blocks
    return None


def detect_backend() -> str:
    """Probe the runtime: Mosaic on TPU, jnp reference elsewhere.

    Interpret-mode Pallas is emulation, not a perf path, so it is never
    auto-selected — tests and parity checks opt in explicitly (via the
    ``REPRO_KERNEL_BACKEND`` env var or a DispatchConfig override).  A
    backend that fails to start raises from ``jax.default_backend()``:
    it is never read as the CPU.
    """
    env = os.environ.get(_ENV_BACKEND, "").strip().lower()
    if env in ("tpu", "interpret", "jnp"):
        return env
    return "tpu" if jax.default_backend() == "tpu" else "jnp"


def resolve_backend(requested: str = "auto") -> str:
    """Map a user/config backend string to a concrete backend."""
    if requested in ("tpu", "interpret", "jnp"):
        return requested
    return detect_backend()


def largest_fitting_block(dim: int, cap: int, multiple_of: int = 1) -> Optional[int]:
    """Largest divisor of ``dim`` that is <= cap and % multiple_of == 0."""
    for c in range(min(cap, dim), 0, -1):
        if dim % c == 0 and c % multiple_of == 0:
            return c
    return None


_ENV_FP8 = "REPRO_FP8_NATIVE"

# TPU generations with a native fp8 MXU dot (Mosaic lowers
# preferred_element_type=f32 over fp8 operands without an upcast);
# earlier chips would silently upcast-and-slow, so the fp8 entries
# decline them and the engine falls back to the dequantize reference
_FP8_TPU_KINDS = ("v6", "v7")


def fp8_native_dot() -> bool:
    """Does the executing TPU contract fp8 x fp8 natively on the MXU?

    Gates the fp8 registry entries on the ``tpu`` backend only —
    ``interpret`` mode always emulates the fp8 bodies on CPU.  The
    ``REPRO_FP8_NATIVE`` env var (1/0) overrides the device-kind probe,
    for new chips the allowlist hasn't caught up with (and for tests).
    """
    env = os.environ.get(_ENV_FP8, "").strip().lower()
    if env in ("1", "true", "yes"):
        return True
    if env in ("0", "false", "no"):
        return False
    kind = jax.devices()[0].device_kind.lower()
    return any(tag in kind for tag in _FP8_TPU_KINDS)


def supports_fp8(backend: str) -> bool:
    """Can this backend execute the *_fp8 entries?  THE one fp8
    capability predicate — the registry entries' ``supported`` hook and
    the benchmark acceptance checks both call it, so the benchmark's
    SKIP decision can never drift from the engine's actual routing.
    interpret mode always emulates; compiled Mosaic execution needs a
    native fp8 MXU dot (:func:`fp8_native_dot`)."""
    return backend != "tpu" or fp8_native_dot()


def dtype_name(dtype) -> str:
    """Canonical dtype name for dispatch reasons, reports, and cache keys.

    Delegates to :func:`repro.kernels.reasons.dtype_name` — the ONE
    dtype-display canonicalization table — and stays exported here for
    back-compat (the engine, benchmarks, and tests import it from the
    registry).
    """
    from repro.kernels import reasons
    return reasons.dtype_name(dtype)
