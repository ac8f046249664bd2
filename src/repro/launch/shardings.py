"""Path-based sharding rules: DP/FSDP on the batch axes, TP/EP/SP on the
model axis.  Every rule degrades to replication when a dim is not evenly
divisible (e.g. vocab 50280 or 504 falls back to d_model-sharded logits).

Parameter rules (leading stacked layer dims are always unsharded):
  embed (V, d)            : V@model  (fallback d@model)
  unembed (d, V)          : V@model  (fallback d@model)
  column-parallel w       : (d, out) -> d@fsdp, out@model   [wq wk wv w_in
                            w_gate wz wx wdt]
  row-parallel w          : (in, d)  -> in@model, d@fsdp    [wo w_out]
  MoE expert stacks       : (E, ..., ...) -> E@model, then FSDP on the
                            widest remaining dim
  compressed sparse values: same rule as the dense w they replace
  meta_packed             : O-dim only (K_c/4 rarely divisible)
  router/norm/conv/scalars: replicated

FSDP = sharding a non-model dim of every weight over the batch axes
(ZeRO-3 equivalent; XLA inserts the per-layer all-gathers).  Optimizer
moments shard identically (they mirror the param tree).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey, SequenceKey

from repro.core.sparse_linear import COLUMN_PARALLEL, ROW_PARALLEL
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.paged import paged_cache_shardings
from repro.models.pjit_utils import AxisEnv

# canonical column/row-parallel name sets live in repro.core.sparse_linear
# (the dispatch engine's shard_map planning keys off the same sets)
KV_PROJ = {"wk", "wv"}


def _key_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if isinstance(k, DictKey):
            names.append(str(k.key))
        elif isinstance(k, SequenceKey):
            names.append(f"[{k.idx}]")
        else:
            names.append(str(k))
    return tuple(names)


def _div(dim: int, mesh, axes) -> bool:
    if axes is None:
        return True
    if isinstance(axes, str):
        axes = (axes,)
    size = int(np.prod([mesh.shape[a] for a in axes]))
    return dim % size == 0


class ShardingRules:
    def __init__(self, env: AxisEnv, cfg: Optional[ModelConfig] = None):
        self.env = env
        self.cfg = cfg
        self.mesh = env.mesh
        self.model = env.model_axis
        bp = env.physical("batch")
        self.fsdp = bp  # tuple or single axis name

    def _kv_shardable(self) -> bool:
        """KV projections shard on model only when whole kv-heads divide the
        axis; otherwise replicate (MQA-style TP) to avoid intra-head splits
        that trigger involuntary SPMD rematerialization."""
        if self.cfg is None or self.cfg.num_kv_heads == 0:
            return True
        return self.cfg.num_kv_heads % self.mesh.shape[self.model] == 0

    def _spec_for_matrix(self, names, shape, n_stack: int) -> P:
        """Sharding for the trailing (matrix) dims of one weight leaf."""
        mesh, model, fsdp = self.mesh, self.model, self.fsdp
        owner = None
        for nm_ in reversed(names):
            if nm_ in COLUMN_PARALLEL or nm_ in ROW_PARALLEL or nm_ in (
                "embed", "unembed", "frame_proj", "router", "conv_w",
            ):
                owner = nm_
                break
        dims = shape[n_stack:]
        lead = (None,) * n_stack
        leaf = names[-1]

        def col2d():  # (in, out): in@fsdp, out@model
            s_in = fsdp if _div(dims[0], mesh, fsdp) else None
            s_out = model if _div(dims[1], mesh, model) else None
            if owner in ("wk", "wv") and not self._kv_shardable():
                s_out = None
            return lead + (s_in, s_out)

        def row2d():  # (in, out): in@model, out@fsdp
            s_in = model if _div(dims[0], mesh, model) else None
            s_out = fsdp if _div(dims[1], mesh, fsdp) else None
            return lead + (s_in, s_out)

        if owner == "embed" or owner == "frame_proj":
            if _div(dims[0], mesh, model):
                return P(*lead, model, None)
            return P(*lead, None, model if _div(dims[1], mesh, model) else None)
        if owner == "unembed":
            if _div(dims[1], mesh, model):
                return P(*lead, None, model)
            return P(*lead, model if _div(dims[0], mesh, model) else None, None)
        if owner in ("router", "conv_w") or owner is None:
            return P(*((None,) * len(shape)))

        is_col = owner in COLUMN_PARALLEL
        if len(dims) == 1:  # bias-like (e.g. dt_bias handled elsewhere)
            return P(*lead, None)
        if leaf in ("w", "values"):
            if len(dims) == 3:  # MoE expert stack (E, in, out)
                e_ax = model if _div(dims[0], mesh, model) else None
                f_in = fsdp if (is_col and _div(dims[1], mesh, fsdp)) else None
                f_out = fsdp if (not is_col and _div(dims[2], mesh, fsdp)) else None
                return P(*lead, e_ax, f_in, f_out)
            return P(*(col2d() if is_col else row2d()))
        if leaf == "meta_packed":
            if len(dims) == 3:
                e_ax = model if _div(dims[0], mesh, model) else None
                return P(*lead, e_ax, None, None)
            # (K_c/4, O): shard O like values' non-model dim? values shard O
            # on model for column-parallel; mirror that when divisible.
            s_out = self.model if (is_col and _div(dims[1], self.mesh, self.model)) else None
            return P(*lead, None, s_out)
        if leaf == "gather_idx":
            return P(*((None,) * len(shape)))
        return P(*((None,) * len(shape)))

    def param_spec(self, path, leaf) -> P:
        names = _key_names(path)
        shape = leaf.shape
        # stacked layer dims: stages/<i>/slotj/... leaves carry (count, repeat)
        n_stack = 2 if (len(names) > 1 and names[0] == "stages") else 0
        # per-head vectors (A_log, D, dt_bias): shard on model when divisible
        if names[-1] in ("A_log", "D", "dt_bias"):
            ax = self.model if _div(shape[-1], self.mesh, self.model) else None
            return P(*((None,) * (len(shape) - 1)), ax)
        if names[-1] == "gamma" or names[-1] == "router":
            return P(*((None,) * len(shape)))
        if len(shape) <= n_stack:  # scalar-ish
            return P(*((None,) * len(shape)))
        matrix_ndim = len(shape) - n_stack
        if matrix_ndim == 1:
            return P(*((None,) * len(shape)))
        return self._spec_for_matrix(names, shape, n_stack)

    def tree_shardings(self, tree) -> Any:
        def fn(path, leaf):
            return NamedSharding(self.mesh, self.param_spec(path, leaf))

        return jax.tree_util.tree_map_with_path(fn, tree)

    # -------------------------------------------------------------- inputs
    def batch_spec(self, tree, global_batch: int) -> Any:
        """Shardings for a train/prefill batch dict: batch dim over DP."""
        bp = self.fsdp  # same physical axes as DP
        ok = _div(global_batch, self.mesh, bp)

        def fn(path, leaf):
            spec = (bp if ok else None,) + (None,) * (len(leaf.shape) - 1)
            return NamedSharding(self.mesh, P(*spec))

        return jax.tree_util.tree_map_with_path(fn, tree)

    def cache_shardings(self, caches, batch: int) -> Any:
        """Decode caches: batch over DP when divisible, else sequence (SP)
        over the whole mesh; kv-heads on model when divisible."""
        mesh, model = self.mesh, self.model
        bp = self.fsdp
        b_ok = _div(batch, mesh, bp)
        all_axes = tuple(mesh.axis_names)

        def fn(path, leaf):
            names = _key_names(path)
            shape = leaf.shape
            leaf_name = names[-1]
            if leaf_name in ("k", "v"):
                # (count, repeat, B, S, Hkv, Dh)
                s_b = bp if b_ok else None
                hkv = shape[4]
                s_h = model if hkv % mesh.shape[model] == 0 else None
                s_seq = None
                if not b_ok:
                    # sequence-parallel cache: S over every non-model axis
                    # (plus model if heads aren't shardable)
                    seq_axes = tuple(a for a in all_axes if a != model)
                    if s_h is None:
                        seq_axes = all_axes
                    s_seq = seq_axes if _div(shape[3], mesh, seq_axes) else None
                return NamedSharding(mesh, P(None, None, s_b, s_seq, s_h, None))
            if leaf_name == "state":
                # (count, repeat, B, nh, ds, hd)
                s_b = bp if b_ok else None
                nh = shape[3]
                s_h = model if nh % mesh.shape[model] == 0 else None
                return NamedSharding(mesh, P(None, None, s_b, s_h, None, None))
            if leaf_name == "conv":
                s_b = bp if b_ok else None
                return NamedSharding(mesh, P(None, None, s_b, None, None))
            return NamedSharding(mesh, P(*((None,) * len(shape))))

        return jax.tree_util.tree_map_with_path(fn, caches)

    def paged_cache_shardings(self, caches) -> Any:
        """Paged decode caches (``repro.models.paged``), placed by the
        models layer's own rule (:func:`repro.models.paged.
        paged_cache_shardings`): KV heads over model where whole heads
        divide it, blocks and positions whole, scales like their pool."""
        return paged_cache_shardings(self.env, caches)


def train_in_shardings(rules: ShardingRules, params_shapes, opt_shapes, batch_shapes,
                       global_batch: int):
    return (
        rules.tree_shardings(params_shapes),
        rules.tree_shardings(opt_shapes),
        rules.batch_spec(batch_shapes, global_batch),
        NamedSharding(rules.mesh, P()),
    )
