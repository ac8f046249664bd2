"""Plain float32 reference of the Mistral decoder, and its seeded weights.

The architecture, as Mistral publishes it for Mistral-Large-Instruct-2407
(the model's ``config.json``, ``model_type: mistral``, and the Mistral 7B
description, arXiv:2310.06825): token embedding; ``num_hidden_layers``
pre-norm blocks of RMSNorm -> grouped-query attention with rotary
positions (rotate-half form) -> residual, RMSNorm -> SwiGLU MLP
``down(silu(gate x) * up x)`` -> residual; a final RMSNorm and an untied
output head.  No biases.  That is InternLM2's block term for term, so the
forward, the seeded weights and the pruning are those of
``bench/reference/internlm2.py``, imported here rather than copied; like
it, this imports nothing of the program under test.

Departures from the published model, each also in the configuration's
``assumed``:

- no sliding window: Mistral-Large states ``sliding_window: null``, so
  attention is full-causal, as the imported forward computes it;
- random weights from the seed in place of the published checkpoint;
- at its widths one layer's float32 weights are 5.5 GB: ``logits_at``
  makes and drops them one layer at a time, so the reference runs on one
  chip once the served tree is freed.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_reference_internlm2_base",
    Path(__file__).resolve().parent / "internlm2.py")
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

HIGHEST = _base.HIGHEST
dims = _base.dims
linear_shapes = _base.linear_shapes
seed_key = _base.seed_key
prune = _base.prune
layer_weights = _base.layer_weights
embedding_weights = _base.embedding_weights
rms_norm = _base.rms_norm
rope = _base.rope
layer_forward = _base.layer_forward
head_logits = _base.head_logits
logits_at = _base.logits_at
gaps = _base.gaps
