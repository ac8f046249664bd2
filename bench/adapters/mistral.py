"""The program's side of the Mistral family: its ``ModelConfig`` and its
parameter tree, holding the very weights ``bench/reference/mistral.py``
makes from the seed.

The tree is InternLM2's (the same linears under the same program names),
so the build is ``bench/adapters/internlm2.py``'s, imported.  What this
adds is the published RMSNorm epsilon: the program has to state it
(``ModelConfig.rms_norm_eps``); a program without that field fails at
set-up (``dataclasses.replace`` refuses the name) rather than serving
another epsilon.
"""

from __future__ import annotations

import dataclasses
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_adapter_internlm2_base",
    Path(__file__).resolve().parent / "internlm2.py")
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

build = _base.build
program_params = _base.program_params


def model_config(cfg: dict):
    """repro's ModelConfig for the configuration file ``cfg``: the
    internlm2 adapter's sizes, and the published ``rms_norm_eps``."""
    return dataclasses.replace(_base.model_config(cfg),
                               rms_norm_eps=float(cfg["rms_norm_eps"]))
