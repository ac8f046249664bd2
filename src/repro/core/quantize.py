"""Low-precision weight quantization for serving layouts (int8 + fp8).

The paper's engine extends the VNNI/TMUL dense low-precision lineage:
tile registers hold narrow values next to 2-bit N:M metadata.  This
module is the storage side of that model for every SparseLinear serving
layout, with the **quantized dtype as a parameter** — the same scale
machinery serves two execution classes:

- ``int8``: symmetric integers in [-127, 127], kernels contract
  int8 x int8 into an exact **int32** accumulator;
- ``fp8`` (``float8_e4m3fn``): 4-bit-mantissa floats up to ±448,
  kernels contract fp8 x fp8 into an **fp32** accumulator
  (``preferred_element_type``), the Mosaic-native mixed-precision path.

In both classes:

- **weights** are quantized offline (at ``convert_layout`` time)
  with **per-output-channel symmetric scales**:
  ``w ~= q.astype(f32) * scale`` with ``scale = absmax(channel) / qmax``
  (``qmax`` = 127 for int8, 448 for fp8 e4m3fn);
- **activations** are quantized dynamically per flattened batch row just
  before a quantized kernel runs (``quantize_rows``), so the MXU
  contracts narrow x narrow into the wide accumulator and the output is
  dequantized once, on the way out:
  ``y = acc * x_scale[:, None] * w_scale[None, :]``.

A quantized layout is an ordinary params dict with one extra ``"scale"``
leaf (``(O,)`` float32), so it checkpoints, shards, and jits like every
other linear layout and ``iter_linear_items`` / the dispatch engine
recognize it structurally.  Which execution class a layout belongs to is
carried by the **value leaf's dtype** (int8 vs float8_e4m3fn) — the
dispatch engine plans on it (see :func:`quant_dtype`).  N:M metadata is
untouched: narrow values + 2-bit indices is exactly the tile-register
storage model the paper assumes, and the compression/pruning step stays
dtype-agnostic.

**Static activation scales** are the decode-side analogue: instead of the
per-row dynamic absmax pass before every quantized contraction,
``repro.serving.prepare`` (with ``static_scales=True``) runs one
forward over a calibration batch, records the per-site activation absmax through the dispatch
engine, and attaches a scalar ``"act_scale"`` leaf to every quantized
linear.  Kernels then quantize activations against the fixed scale —
no reduction over the row on the decode hot path — and the scale rides
the params tree (replicated under any mesh) like every other leaf.

See ``docs/quantization.md`` for the full serving guide (scale layouts,
calibration workflow, and the sharded pmax/psum/dequantize ordering).
"""

from __future__ import annotations

import contextlib
import functools
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "SCALE_KEY",
    "ACT_SCALE_KEY",
    "QUANT_DTYPES",
    "canonical_qdtype",
    "is_quantized",
    "is_quantized_dtype",
    "quant_dtype",
    "qmax",
    "has_static_scales",
    "is_linear_leaf",
    "quantize_per_channel",
    "dequantize",
    "quantize_rows",
    "quantize_rows_static",
    "quantize_linear",
    "calibration_active",
    "record_calibration",
]

SCALE_KEY = "scale"
ACT_SCALE_KEY = "act_scale"
_CALIB_KEY = "calib_id"

# names that have already fired their one DeprecationWarning this process.
# Tests reset this (``_DEPRECATION_WARNED.clear()``) to re-arm a shim.
_DEPRECATION_WARNED: set = set()


def warn_deprecated_once(name: str, hint: str) -> None:
    """Fire ``DeprecationWarning`` for ``name`` once per process.

    Retired call spellings (today: the kwarg form of
    ``repro.kernels.dispatch.plan``) funnel through here so old call
    sites keep working but nudge — once, not per call — toward the
    canonical API.
    """
    if name in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(name)
    warnings.warn(f"{name} is deprecated; {hint}",
                  DeprecationWarning, stacklevel=3)

# keys a linear layout may carry on top of its structural ones; the
# structural detection must stay blind to them
_AUX_KEYS = {SCALE_KEY, ACT_SCALE_KEY, _CALIB_KEY}

# the quantized execution classes and their symmetric dynamic range:
# int8 keeps [-127, 127] (-128 unused); fp8 e4m3fn saturates at ±448
# (the format has no inf — an unclipped overflow casts to NaN, so every
# quantizer here clips BEFORE the cast)
QUANT_DTYPES: Dict[Any, float] = {
    jnp.dtype(jnp.int8): 127.0,
    jnp.dtype(jnp.float8_e4m3fn): 448.0,
}

# user-facing aliases (launcher flags, convert_layout targets)
_DTYPE_ALIASES = {
    "int8": jnp.int8,
    "fp8": jnp.float8_e4m3fn,
    "float8_e4m3fn": jnp.float8_e4m3fn,
}


def canonical_qdtype(dtype):
    """Normalize a quantized-dtype spec ("int8" | "fp8" | a dtype) to the
    jnp dtype, or raise ValueError for anything outside the table."""
    if isinstance(dtype, str):
        if dtype not in _DTYPE_ALIASES:
            raise ValueError(
                f"unknown quantize target {dtype!r} "
                f"(expected one of {sorted(_DTYPE_ALIASES)})")
        dtype = _DTYPE_ALIASES[dtype]
    dt = jnp.dtype(dtype)
    if dt not in QUANT_DTYPES:
        raise ValueError(f"{dt.name} is not a quantized execution dtype "
                         f"(expected one of "
                         f"{sorted(d.name for d in QUANT_DTYPES)})")
    return dt


def is_quantized_dtype(dtype) -> bool:
    """True for the narrow storage dtypes the engine plans as quantized."""
    try:
        return jnp.dtype(dtype) in QUANT_DTYPES
    except TypeError:
        return False


def qmax(dtype) -> float:
    """Symmetric dynamic range of one quantized dtype (127 / 448)."""
    return QUANT_DTYPES[canonical_qdtype(dtype)]


def is_quantized(params: Dict[str, Any]) -> bool:
    """Structural test: quantized layouts carry a per-channel scale leaf."""
    return isinstance(params, dict) and SCALE_KEY in params


def quant_dtype(params: Dict[str, Any]):
    """The quantized execution dtype of one layout (int8 | float8_e4m3fn),
    or ``None`` for float layouts.  THE dispatch axis: the engine plans a
    quantized problem on its value leaf's storage dtype."""
    if not is_quantized(params):
        return None
    key = "w" if "w" in params else "values"
    dt = jnp.dtype(params[key].dtype)
    return dt if dt in QUANT_DTYPES else None


def has_static_scales(params: Dict[str, Any]) -> bool:
    """True when the leaf carries a calibrated static activation scale."""
    return isinstance(params, dict) and ACT_SCALE_KEY in params


def is_linear_leaf(tree: Any) -> bool:
    """One flat SparseLinear layout dict (dense ``{"w"}`` possibly with a
    ``scale``, compressed, or gather).  THE shared structural detection:
    ``dispatch.iter_linear_items`` and :func:`_quantize_tree` both key off
    it, so the engine's tree walk and the quantizer cannot drift.  A
    rowwise container is NOT a leaf here — its nested tier segments are
    (the walker recurses; the quantizer handles the nest explicitly).
    """
    return isinstance(tree, dict) and (
        "meta_packed" in tree or "gather_idx" in tree
        or set(tree) - _AUX_KEYS == {"w"})


def _cast_quantized(x32: jax.Array, dtype) -> jax.Array:
    """f32 values (already divided by their scale) -> the narrow dtype.

    int8 rounds-to-nearest explicitly; fp8 relies on the cast's
    round-to-nearest-even.  Both clip to the symmetric range first —
    for fp8 e4m3fn an unclipped overflow would cast to NaN (the format
    has no inf), which would silently poison the accumulator.
    """
    dt = canonical_qdtype(dtype)
    q = jnp.clip(x32, -QUANT_DTYPES[dt], QUANT_DTYPES[dt])
    if dt == jnp.dtype(jnp.int8):
        q = jnp.round(q)
    return q.astype(dt)


@jax.jit
def _channel_absmax(w: jax.Array) -> jax.Array:
    return jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _cast_to_scale(w: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return _cast_quantized(w.astype(jnp.float32) / scale[..., None, :], dtype)


def quantize_per_channel(
    w: jax.Array, dtype=jnp.int8
) -> Tuple[jax.Array, jax.Array]:
    """Symmetric quantization along the contraction axis.

    ``w``: ``(..., K, O)`` float weights (leading dims are stacked
    layers).  Returns ``(q, scale)`` with ``q`` of the requested narrow
    ``dtype`` (int8 | fp8) in the same shape and ``scale`` ``(..., O)``
    float32 such that ``dequantize(q, scale) ~= w`` with per-channel
    absolute error bounded by the dtype's step at the channel absmax
    (``absmax/127`` for int8; one fp8 ulp at absmax — tighter for most
    of the distribution, since fp8 steps shrink toward zero).

    The two passes over ``w`` are jitted, so their float32 copies fuse
    away and a stacked leaf quantizes beside its narrow result alone (at
    Mistral-Large's widths an eager pass held two float32 copies of a
    1.9 GB bfloat16 leaf per chip beside the whole tree).  The scale
    between them stays eager: jitted, ``absmax / qmax`` would become a
    multiply by the constant's reciprocal and move some values a step.
    """
    dt = canonical_qdtype(dtype)
    absmax = _channel_absmax(w)                               # (..., O)
    # floor AFTER the division: tiny/qmax is a denormal that XLA may
    # flush to zero, which would turn all-zero channels into 0/0 = NaN
    scale = jnp.maximum(absmax / QUANT_DTYPES[dt],
                        jnp.finfo(jnp.float32).tiny)
    return _cast_to_scale(w, scale, dt), scale.astype(jnp.float32)


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    """``(..., K, O)`` narrow values + ``(..., O)`` scales -> f32 weights."""
    return q.astype(jnp.float32) * scale[..., None, :]


def quantize_rows(
    x: jax.Array, absmax: Optional[jax.Array] = None, dtype=jnp.int8
) -> Tuple[jax.Array, jax.Array]:
    """Dynamic per-row symmetric quantization of activations.

    ``x``: ``(B, K)`` float.  Returns ``(x_q, x_scale)`` with ``x_q``
    of the narrow ``dtype`` ``(B, K)`` and ``x_scale`` ``(B, 1)``
    float32.  All-zero rows (idle batch slots) get a tiny nonzero scale
    so the division is safe.

    ``absmax`` overrides the per-row reduction — the sharded execution
    class passes the pmax-lifted GLOBAL row absmax so every contraction
    shard quantizes against one coherent scale (same rounding, same
    epsilon: the single source of the quantization numerics).
    """
    dt = canonical_qdtype(dtype)
    x32 = x.astype(jnp.float32)
    if absmax is None:
        absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)   # (B, 1)
    # same denormal-flush guard as quantize_per_channel: floor the
    # DIVIDED scale so all-zero rows never divide by a flushed zero
    scale = jnp.maximum(absmax / QUANT_DTYPES[dt],
                        jnp.finfo(jnp.float32).tiny)
    return _cast_quantized(x32 / scale, dt), scale


def quantize_rows_static(
    x: jax.Array, act_scale: jax.Array, dtype=jnp.int8
) -> Tuple[jax.Array, jax.Array]:
    """Static-scale quantization of activations (decode fast path).

    ``act_scale`` is the scalar calibrated scale attached by
    serving-prep calibration; no per-row reduction runs —
    the whole absmax pass :func:`quantize_rows` does per call is skipped.
    Values beyond the calibrated range saturate at ±qmax (standard
    static quantization semantics).  Returns ``(x_q, x_scale)`` with
    ``x_scale`` broadcast to the ``(B, 1)`` layout the kernels expect.
    """
    dt = canonical_qdtype(dtype)
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(act_scale.astype(jnp.float32).reshape(()),
                        jnp.finfo(jnp.float32).tiny)
    xs = jnp.full((x.shape[0], 1), scale, jnp.float32)
    return _cast_quantized(x32 / scale, dt), xs


def quantize_linear(params: Dict[str, Any], dtype=jnp.int8) -> Dict[str, Any]:
    """Quantize one SparseLinear serving leaf (any layout) to ``dtype``.

    dense ``{"w"}``, compressed ``{"values", "meta_packed"}`` and gather
    ``{"values", "gather_idx"}`` layouts all quantize their float operand
    per output channel; metadata/index leaves pass through unchanged.
    Rowwise layouts quantize each nested tier segment with its own
    scales.  Idempotent: an already-quantized leaf is returned as-is.
    """
    if is_quantized(params):
        return params
    if "rowwise" in params:
        return {
            "rowwise": {k: quantize_linear(v, dtype)
                        for k, v in params["rowwise"].items()},
            "inv_perm": params["inv_perm"],
        }
    key = "w" if "w" in params else "values"
    q, scale = quantize_per_channel(params[key], dtype)
    out = dict(params)
    out[key] = q
    out[SCALE_KEY] = scale
    return out


def _quantize_tree(tree, dtype=jnp.int8):
    """Quantize every SparseLinear leaf in a model params tree.

    ``dtype`` may be a jnp dtype or an alias string ("int8" | "fp8").
    Keys off :func:`is_linear_leaf` — the same structural detection
    ``dispatch.iter_linear_items`` uses — so embeddings, norms, routers,
    and other raw-array leaves are left untouched.  Stacked-layer leading
    dims are preserved (scales become ``(L, O)``).
    """
    dt = canonical_qdtype(dtype)
    return map_linear_leaves(tree, lambda leaf: quantize_linear(leaf, dt))


def map_linear_leaves(tree, fn: Callable[[Dict[str, Any]], Dict[str, Any]]):
    """Rebuild a params tree with ``fn`` applied to every SparseLinear
    leaf dict (rowwise tier segments included, via ``quantize_linear``-
    style recursion for the nest).  The traversal mirrors
    ``dispatch.iter_linear_items``' structural detection, so anything the
    engine would dispatch is exactly what gets mapped."""
    if isinstance(tree, dict):
        if "rowwise" in tree:
            return {
                "rowwise": {k: fn(v) for k, v in tree["rowwise"].items()},
                **{k: v for k, v in tree.items() if k != "rowwise"},
            }
        if is_linear_leaf(tree):
            return fn(tree)
        return {k: map_linear_leaves(v, fn) for k, v in tree.items()}
    if isinstance(tree, list):
        return [map_linear_leaves(v, fn) for v in tree]
    if isinstance(tree, tuple):
        return tuple(map_linear_leaves(v, fn) for v in tree)
    return tree


# ---------------------------------------------------------------------------
# static activation-scale calibration
# ---------------------------------------------------------------------------
#
# The dispatch engine cannot know a linear's identity from inside a jitted/
# scanned trace, so calibration threads a per-site integer tag through the
# params tree itself: each quantized leaf gets a ``calib_id`` leaf whose
# leading dims broadcast with the layer/expert stacking (scans slice it down
# to a scalar by call time), and ``sparse_matmul`` reports (id, absmax(x))
# pairs through an io_callback while the calibration context is active.
#
# The active store lives in a module-level slot that the callback resolves
# AT RUN TIME, not a closure captured at trace time: a jitted batch_fn is
# traced once and cached, so a closure would bake the FIRST calibration's
# store into the jaxpr and every later calibration through the cached
# function would silently write to a discarded dict (n_sites == 0).  The
# slot is also what makes the callback safe on JAX's callback thread — a
# threading.local would read as unset there.

_ACTIVE_STORE: list = [None]


def calibration_active() -> bool:
    return _ACTIVE_STORE[0] is not None


@contextlib.contextmanager
def _calibrating(store: Dict[int, float]):
    # one process-global slot means one calibration at a time: a second
    # concurrent calibration would interleave its absmaxes into this
    # store (silent accuracy corruption), so fail loudly instead
    if _ACTIVE_STORE[0] is not None:
        raise RuntimeError(
            "a calibration is already active in this process — "
            "calibration passes cannot run concurrently "
            "(the engine's io_callback resolves one process-global store)")
    _ACTIVE_STORE[0] = store
    try:
        yield store
    finally:
        _ACTIVE_STORE[0] = None


def _fold(i, a) -> None:
    store = _ACTIVE_STORE[0]
    if store is None:
        return   # baked into a cached trace, re-run outside calibration
    key = int(i)
    store[key] = max(store.get(key, 0.0), float(a))


def record_calibration(calib_id: jax.Array, x: jax.Array) -> None:
    """Record ``absmax(x)`` for one tagged linear site (engine hook).

    Runs inside traced code (scan bodies included): the io_callback fires
    per executed call with concrete values and folds the running max into
    whatever store is active WHEN IT FIRES.  No-op without an active
    calibration context.
    """
    if not calibration_active():
        return
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    jax.debug.callback(_fold, calib_id.reshape(()), absmax, ordered=True)


def _calibrate_activation_scales(
    params,
    batch_fn: Callable[[Any], Any],
) -> Tuple[Any, int]:
    """Attach static activation scales to every quantized linear leaf.

    ``params`` is a (possibly layer-stacked) serving params tree whose
    linears are already quantized (``repro.serving.prepare`` /
    ``convert_layout(..., quantize="int8"|"fp8")``).  ``batch_fn``
    runs one representative forward over the calibration batch given a
    params tree — e.g. ``lambda p: forward(p, cfg, tokens=batch)`` —
    while the engine records, per linear site, the max |activation| it
    contracts.

    Returns ``(params_with_scales, n_calibrated)``: every observed site
    gains a scalar ``act_scale = absmax / qmax`` leaf (``qmax`` follows
    the site's own storage dtype, so int8 and fp8 leaves can coexist in
    one tree; stacked layers and expert stacks share one scale — the max
    over all their activations, the conservative choice); sites the batch
    never exercised keep the dynamic per-row path.  Decode then skips the
    per-row absmax pass entirely (see :func:`quantize_rows_static`).
    """
    counter = [0]

    def _tag(leaf: Dict[str, Any]) -> Dict[str, Any]:
        if not is_quantized(leaf):
            return leaf
        key = "w" if "w" in leaf else "values"
        lead = leaf[key].shape[:-2]
        out = dict(leaf)
        out[_CALIB_KEY] = jnp.full(lead, counter[0], jnp.int32)
        counter[0] += 1
        return out

    tagged = map_linear_leaves(params, _tag)
    store: Dict[int, float] = {}
    with _calibrating(store):
        jax.block_until_ready(batch_fn(tagged))
        # the debug callbacks run on JAX's callback thread and are not
        # ordered with the output arrays — without this barrier a jitted
        # batch_fn can leave _fold calls in flight and silently
        # under-calibrate
        jax.effects_barrier()

    counter[0] = 0

    def _attach(leaf: Dict[str, Any]) -> Dict[str, Any]:
        if not is_quantized(leaf):
            return leaf
        site = counter[0]
        counter[0] += 1
        if site not in store:
            return leaf          # never exercised: stays dynamic
        out = dict(leaf)
        # the scale follows the leaf's own storage dtype (int8 -> /127,
        # fp8 -> /448) and broadcasts over the stacked leading dims
        # (layer scans slice every leaf, so a bare scalar would break
        # lax.scan over the stack)
        key = "w" if "w" in leaf else "values"
        dt = quant_dtype(leaf) or jnp.dtype(jnp.int8)
        out[ACT_SCALE_KEY] = jnp.full(leaf[key].shape[:-2],
                                      max(store[site], 0.0) / QUANT_DTYPES[dt],
                                      jnp.float32)
        return out

    return map_linear_leaves(params, _attach), len(store)
