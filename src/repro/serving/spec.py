"""``ServingSpec`` + ``prepare``: the one offline-prep entry point.

Before this module, preparing weights for serving meant composing four
ad-hoc steps by hand — per-leaf layout conversion + quantization,
whole-model tree walks, activation-scale calibration for static scales,
and a ``DispatchConfig`` + mesh placement dance copied between
``launch/serve.py``, the examples, and the benchmarks.  Now:

```python
prepared = repro.serving.prepare(params, ServingSpec(layout="compressed",
                                                     sparsity=(2, 4),
                                                     qdtype="int8"))
```

does all of it, in the documented order (layout conversion -> weight
quantization -> activation-scale calibration -> mesh placement).  The
old per-piece entry points (``convert_to_serving``, ``quantize_tree``,
``calibrate_activation_scales``) went through a warn-once deprecation
cycle and have been removed; ``convert_layout`` remains the offline
single-leaf primitive this pipeline composes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Optional, Tuple

_LAYOUTS = ("dense", "compressed", "gather", "rowwise")
_ADMISSION = ("reserve", "optimistic")
_BACKENDS = ("auto", "tpu", "interpret", "jnp")


@dataclasses.dataclass(frozen=True)
class ServingSpec:
    """Frozen description of how a model serves.

    Offline-prep axes (consumed by :func:`prepare`):

    - ``layout``: SparseLinear serving layout for every linear
      (``dense | compressed | gather | rowwise``).
    - ``sparsity``: ``(n, m)`` N:M pattern, or ``None`` for dense 4:4.
    - ``qdtype``: weight quantization dtype (``"int8" | "fp8"`` | None).
    - ``static_scales``: calibrate static activation scales (needs a
      model config + calibration tokens at :func:`prepare` time).
    - ``mesh``: ``(data, model)`` mesh shape, or None for single-device.
    - ``backend`` / ``autotune``: dispatch-engine knobs.

    Engine axes (consumed by :class:`repro.serving.Engine`):

    - ``slots``: decode batch width (concurrent streams).
    - ``max_len``: per-request position ceiling (block-table width is
      ``ceil(max_len / block_len)``).
    - ``block_len``: tokens per KV block.
    - ``kv_blocks``: total allocatable KV blocks (the HBM budget knob);
      None -> enough for every slot at ``max_len`` (no eviction ever).
    - ``kv_qdtype``: KV-cache quantization dtype (``"int8" | "fp8"`` |
      None), riding the same per-leaf scale machinery as weights.
    - ``admission``: ``"reserve"`` admits only when a request's
      worst-case block count is free (never evicts); ``"optimistic"``
      admits on prompt-sized headroom and preempts (recompute-style,
      LIFO victim) when the pool runs dry.
    - ``prefill_chunk``: max prompt tokens per prefill call.
    """

    layout: str = "dense"
    sparsity: Optional[Tuple[int, int]] = None
    qdtype: Optional[str] = None
    static_scales: bool = False
    mesh: Optional[Tuple[int, int]] = None
    backend: str = "auto"
    autotune: bool = False
    slots: int = 4
    max_len: int = 64
    block_len: int = 8
    kv_blocks: Optional[int] = None
    kv_qdtype: Optional[str] = None
    admission: str = "reserve"
    prefill_chunk: int = 8

    def __post_init__(self):
        if self.layout not in _LAYOUTS:
            raise ValueError(f"layout {self.layout!r} not in {_LAYOUTS}")
        if self.admission not in _ADMISSION:
            raise ValueError(f"admission {self.admission!r} not in {_ADMISSION}")
        if self.backend not in _BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {_BACKENDS}")
        if self.static_scales and self.qdtype is None:
            raise ValueError("static_scales requires qdtype ('int8' | 'fp8')")
        for dt in (self.qdtype, self.kv_qdtype):
            if dt is not None:
                from repro.core.quantize import canonical_qdtype
                canonical_qdtype(dt)    # raises on unknown targets
        if self.sparsity is not None:
            n, m = self.sparsity
            if not (0 < n <= m):
                raise ValueError(f"sparsity {self.sparsity} needs 0 < n <= m")
        if self.block_len <= 0 or self.prefill_chunk <= 0 or self.slots <= 0:
            raise ValueError("block_len, prefill_chunk, slots must be positive")
        if self.max_len < self.block_len:
            raise ValueError("max_len must cover at least one block")

    @property
    def sparsity_config(self):
        from repro.core.sparse_linear import SparsityConfig
        if self.sparsity is None:
            return SparsityConfig(mode=self.layout)
        n, m = self.sparsity
        return SparsityConfig(n=n, m=m, mode=self.layout)

    @property
    def table_width(self) -> int:
        return math.ceil(self.max_len / self.block_len)

    def default_kv_blocks(self) -> int:
        """Budget that can hold every slot at max_len (never evicts)."""
        return self.slots * self.table_width

    def apply_to(self, cfg):
        """Model config with this spec's sparsity/layout installed —
        call before ``init_params`` so weights are born in the serving
        layout (compression is an offline step, exactly as in the paper).
        """
        return cfg.with_sparsity(self.sparsity_config)


@dataclasses.dataclass
class Prepared:
    """Output of :func:`prepare`: serving-ready params + runtime context.

    ``params`` are converted / quantized / calibrated / mesh-placed;
    ``activate()`` installs the mesh env and dispatch override for the
    duration of a serving loop (both :class:`Engine` and the lockstep
    baseline route through it, so flags behave identically).
    """

    params: Any
    spec: ServingSpec
    cfg: Any = None               # ModelConfig, when preparing a full model
    sp_cfg: Any = None            # SparsityConfig actually in effect
    dispatch: Any = None          # kernels.dispatch.DispatchConfig
    axis_env: Any = None          # launch mesh env (None off-mesh)
    mesh: Any = None
    calibrated_sites: int = 0

    @contextlib.contextmanager
    def activate(self):
        from repro.kernels import dispatch as kdispatch
        with contextlib.ExitStack() as stack:
            if self.axis_env is not None:
                from repro.models.pjit_utils import use_axis_env
                stack.enter_context(use_axis_env(self.axis_env))
            stack.enter_context(kdispatch.use_dispatch(
                backend=self.spec.backend, autotune=self.spec.autotune))
            yield self

    def audit(self, backend: str = "tpu"):
        """Static plan audit of this prepared model's (cfg, spec) pair
        (:func:`repro.analysis.audit_model`) — the weight-free
        counterpart of :meth:`dispatch_report`, with reason codes and
        lint findings instead of display lines.  Requires ``cfg`` (full
        -model preparation)."""
        if self.cfg is None:
            raise ValueError("Prepared.audit() needs a full-model "
                             "preparation (prepare(..., cfg=cfg))")
        from repro.analysis import audit_model
        return audit_model(self.cfg, self.spec, backend=backend,
                           arch=getattr(self.cfg, "name", ""))

    def dispatch_report(self, batches: Optional[Tuple[int, ...]] = None):
        """Engine-decision lines for this tree (see
        :func:`repro.kernels.dispatch.dispatch_report`)."""
        from repro.kernels import dispatch as kdispatch
        if batches is None:
            batches = (self.spec.slots, self.spec.prefill_chunk)
        with self.activate():
            return kdispatch.dispatch_report(
                self.params, batches, self.sp_cfg, dispatch=self.dispatch)


def prepare(
    params,
    spec: ServingSpec,
    *,
    cfg=None,
    calib_tokens=None,
) -> Prepared:
    """Prepare a params tree for serving under ``spec``.

    Composes, in order:

    1. **layout conversion** — any linear leaf still holding a dense
       ``{"w"}`` is converted to ``spec.layout``
       (:func:`repro.core.sparse_linear.convert_layout`); leaves already
       in a serving layout pass through.
    2. **weight quantization** — ``spec.qdtype`` quantizes every layout's
       float operand with per-channel scales (idempotent).
    3. **activation-scale calibration** — ``spec.static_scales`` runs one
       forward over ``calib_tokens`` (requires ``cfg``) and attaches
       static ``act_scale`` leaves so decode skips the per-row absmax.
    4. **mesh placement** — ``spec.mesh`` builds the (data, model) mesh,
       applies the sharding rules (requires ``cfg``), and records the
       axis env that ``Prepared.activate()`` installs.

    ``params`` may be a full model tree (pass ``cfg``) or a bare layout
    leaf / small tree (benchmarks, unit tests) with ``cfg=None``.
    """
    import jax

    from repro.core.quantize import map_linear_leaves
    from repro.core.sparse_linear import convert_layout
    from repro.kernels import dispatch as kdispatch

    sp_cfg = cfg.sparsity if cfg is not None else spec.sparsity_config

    def _prep_leaf(leaf):
        return convert_layout(leaf, sp_cfg, spec.layout, quantize=spec.qdtype)

    params = map_linear_leaves(params, _prep_leaf)

    calibrated = 0
    if spec.static_scales:
        # a tree loaded from a conversion artifact already carries its
        # calibrated act_scale leaves — count them instead of demanding
        # calibration data the offline pipeline already consumed
        from repro.core.quantize import has_static_scales, is_quantized
        need, have = [0], [0]

        def _scan(leaf):
            if is_quantized(leaf):
                (have if has_static_scales(leaf) else need)[0] += 1
            return leaf

        map_linear_leaves(params, _scan)
        if need[0] == 0 and have[0] > 0:
            calibrated = have[0]
        else:
            if cfg is None or calib_tokens is None:
                raise ValueError(
                    "static_scales needs cfg= and calib_tokens= at prepare() "
                    "time (one representative prefill batch)")
            from repro.core.quantize import _calibrate_activation_scales
            from repro.models import forward
            params, calibrated = _calibrate_activation_scales(
                params, lambda p: forward(p, cfg, tokens=calib_tokens))

    axis_env = mesh = None
    if spec.mesh is not None:
        if cfg is None:
            raise ValueError("mesh placement needs cfg= (sharding rules "
                             "are model-config driven)")
        from repro.launch.mesh import make_axis_env, make_mesh
        from repro.launch.shardings import ShardingRules
        d_, m_ = spec.mesh
        mesh = make_mesh((d_, m_), ("data", "model"))
        axis_env = make_axis_env(mesh)
        rules = ShardingRules(axis_env, cfg)
        params = jax.device_put(params, rules.tree_shardings(params))

    dcfg = kdispatch.DispatchConfig(backend=spec.backend,
                                    autotune=spec.autotune)
    return Prepared(params=params, spec=spec, cfg=cfg, sp_cfg=sp_cfg,
                    dispatch=dcfg, axis_env=axis_env, mesh=mesh,
                    calibrated_sites=calibrated)


def prepare_from_artifact(
    path,
    *,
    backend: Optional[str] = None,
    autotune: Optional[bool] = None,
    mesh: Optional[Tuple[int, int]] = None,
    calib_tokens=None,
) -> Prepared:
    """Load a conversion artifact (``python -m repro.launch.convert``)
    and stand it up for serving.

    The artifact's manifest is the recipe: the model config rebuilds
    from its ``config`` block, the :class:`ServingSpec` from its
    ``spec`` block, and the params tree comes back already pruned /
    compressed / quantized / calibrated — :func:`prepare` then runs as
    an idempotent pass (converted leaves pass through; artifact-borne
    ``act_scale`` leaves satisfy ``static_scales`` without calibration
    data).  ``backend`` / ``autotune`` / ``mesh`` override the frozen
    spec for the serving machine at hand.
    """
    from repro.analysis.budget import config_from_manifest, spec_from_manifest
    from repro.checkpoint import load_artifact

    params, manifest = load_artifact(path)
    cfg = config_from_manifest(manifest)
    spec = spec_from_manifest(manifest)
    over: dict = {}
    if backend is not None:
        over["backend"] = backend
    if autotune is not None:
        over["autotune"] = autotune
    if mesh is not None:
        over["mesh"] = tuple(mesh)
    if over:
        spec = dataclasses.replace(spec, **over)
    cfg = spec.apply_to(cfg)
    return prepare(params, spec, cfg=cfg, calib_tokens=calib_tokens)
