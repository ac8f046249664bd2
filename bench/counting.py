"""Operations and bytes the served model needs, from shapes alone.

Kept with the benchmark so that every PR counts the same way.  Model
FLOPs count the configuration's mathematics, whatever implements it:
the nonzero weights of its layout, attention over live positions only,
and the output head only where a token is sampled.  A kernel call's
bytes are its stored operands and its rows in and out.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable, Optional, Sequence, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"

# bytes per stored element
_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1,
         "float8_e4m3fn": 1, "fp8": 1}


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(path.read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def density(sparsity: Optional[Sequence[int]]) -> float:
    return 1.0 if sparsity is None else sparsity[0] / sparsity[1]


def linear_flops(k: int, o: int, rows: int, sparsity=None) -> float:
    """Multiply-adds x 2 over the nonzero weights of a (k, o) linear."""
    return 2.0 * rows * k * o * density(sparsity)


def stored_weight_bytes(k: int, o: int, layout: str, sparsity=None,
                        qdtype: Optional[str] = None,
                        dtype: str = "bfloat16") -> int:
    """Bytes a (k, o) linear occupies in the program's layout: values,
    N:M positions packed four 2-bit entries to a byte, and a float32
    scale per output channel when quantized."""
    item = _ITEM[qdtype or dtype]
    scale = 4 * o if qdtype else 0
    if layout == "dense":
        return k * o * item + scale
    if layout == "compressed":
        n, m = sparsity
        kc = k * n // m
        return kc * o * item + (kc // 4) * o + scale
    raise ValueError(f"no byte count for layout {layout!r}")


def kernel_call(k: int, o: int, rows: int, layout: str, sparsity=None,
                qdtype=None, weights: int = 1, act_bytes: int = 2,
                out_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one linear-kernel call over ``rows`` rows that
    contracts ``weights`` (k, o) operands (2 for a fused gate-up) against
    one activation tile."""
    flops = weights * linear_flops(k, o, rows, sparsity if layout != "dense"
                                   else None)
    nbytes = (weights * stored_weight_bytes(k, o, layout, sparsity, qdtype)
              + rows * k * act_bytes + rows * o * out_bytes)
    return flops, float(nbytes)


def least_time(flops: float, nbytes: float, peak: dict,
               int8: bool = False) -> float:
    """Seconds the chip needs at best: the larger of compute and memory."""
    rate = peak["int8_ops_per_s"] if int8 else peak["bf16_flops_per_s"]
    return max(flops / rate, nbytes / peak["hbm_bytes_per_s"])


def request_flops(prompt: int, new: int, shapes, layers: int, heads: int,
                  head_dim: int, hidden: int, vocab: int,
                  sparsity=None) -> float:
    """A served request: every prompt token and every fed-back output
    token through the model, and the output head for each sampled token."""
    lin = sum(linear_flops(k, o, 1, sparsity) for k, o in shapes.values())
    fed = prompt + max(new - 1, 0)                 # positions 0 .. fed-1
    attn_positions = fed * (fed + 1) / 2           # sum of (p + 1)
    body = layers * (lin * fed + 4.0 * heads * head_dim * attn_positions)
    return body + new * 2.0 * hidden * vocab


def window_flops(done: Iterable[Tuple[int, int]], shapes, layers: int,
                 heads: int, head_dim: int, hidden: int, vocab: int,
                 sparsity=None) -> float:
    """Model FLOPs of every finished ``(prompt_len, new_tokens)``."""
    return sum(request_flops(p, n, shapes, layers, heads, head_dim, hidden,
                             vocab, sparsity) for p, n in done)
