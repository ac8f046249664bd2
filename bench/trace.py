"""From a profiler trace of the chip to the numbers the readers report.

``jax.profiler`` writes one ``.xplane.pb`` per host.  On a TPU v5e its
device planes (``/device:TPU:<n>``) hold a line ``XLA Ops``, whose
events are named by their whole HLO instruction text (operand and
result shapes included; a ``while`` holds the operations of its body),
and a line ``XLA Modules`` of program executions
(``jit_paged_decode_step(<fingerprint>)``), with start and duration in
nanoseconds.  The host plane holds what the host threads were doing,
Python frames on the line named after the interpreter (``python3``).  From these:

- busy time: the union of the operation intervals, averaged over the
  device planes that ran anything, as are linear time and each
  operation kind's time: every number is one chip's;
- idle gaps: the gaps in the union of every plane's operations, when
  no chip ran anything;
- program time: each execution of a jitted program, by its name;
- kernel classes: every Pallas kernel (an operation whose text names
  ``custom_call_target="tpu_custom_call"``) is matched against the
  class files in ``bench/kernels/``; one that no class claims, or that
  two claim, fails the reduction, so that no kernel is counted in the
  wrong bucket;
- for the linear class, each call's FLOPs and bytes, from the operand
  and result shapes its HLO text states: the FLOPs are 2 x rows x the
  weight values it contracts, the bytes are everything it reads and
  writes once.  A call whose shapes are not a linear's fails.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from .counting import least_time

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PALLAS = 'custom_call_target="tpu_custom_call"'
# operations that hold others: their time is their body's
CONTAINERS = ("while", "conditional", "call")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|bf16|f16|f32|f8e4m3fn|"
                    r"f8e5m2|s4|u4)\[([0-9,]*)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4,
          "u32": 4, "bf16": 2, "f16": 2, "f32": 4, "f8e4m3fn": 1,
          "f8e5m2": 1, "s4": 0.5, "u4": 0.5}
# weight values: the operands whose elements are multiplied (not the
# packed N:M positions, which are unsigned bytes)
_VALUE_TYPES = ("bf16", "f16", "f32", "s8", "f8e4m3fn", "f8e5m2")


class TraceError(RuntimeError):
    """The trace holds something the reduction cannot account for."""


@dataclasses.dataclass
class Op:
    name: str           # on a TPU trace, the operation's whole HLO text
    start_ns: float
    dur_ns: float

    @property
    def kind(self) -> str:
        """``%fusion.12 = ...`` -> ``fusion``."""
        head = self.name.split(" = ")[0].lstrip("%")
        return head.rsplit(".", 1)[0] if head.rsplit(".", 1)[-1].isdigit() \
            else head


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    programs: Dict[str, List[float]]
    linear_calls: int
    linear_s: float
    linear_least_s: Optional[float]
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.top_ops[:10]],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """Every ``type[dims]`` in an HLO text, in order."""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def nbytes(t: str, dims: Tuple[int, ...]) -> float:
    n = 1
    for d in dims:
        n *= d
    return n * _BYTES[t]


def linear_counts(hlo: str) -> Tuple[float, float]:
    """(FLOPs, bytes) of one linear-kernel call from its HLO text: the
    result ``(rows, out)`` first, then the operands, the activation
    ``(rows, in)`` first among them."""
    found = shapes(hlo)
    if len(found) < 3:
        raise TraceError(f"no operand shapes in {hlo[:200]!r}")
    # the text may state the operands twice (inline and as layout
    # constraints): keep the first result and one copy of the operands
    result, rest = found[0], found[1:]
    ops = _dedupe_operands(rest)
    (xt, xd) = ops[0]
    rows = xd[0]
    weights = [(t, d) for t, d in ops[1:]
               if t in _VALUE_TYPES and len(d) == 2 and d[0] > 1
               and d[1] == result[1][-1]]
    if not weights or result[1][0] != rows:
        raise TraceError(f"not a linear call: {hlo[:300]!r}")
    flops = 2.0 * rows * sum(d[0] * d[1] for _, d in weights)
    moved = sum(nbytes(t, d) for t, d in ops) + nbytes(*result)
    return flops, moved


def _dedupe_operands(found):
    half = len(found) // 2
    if len(found) % 2 == 0 and found[:half] == found[half:]:
        return found[:half]
    return found


def _program(name: str) -> str:
    base = name.split("(")[0]
    return base[4:] if base.startswith("jit_") else base


def load_ops(path: Path):
    """The operation and program events of each device plane, and the
    host plane's events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "SparseCore" not in \
                plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dest = ops if line.name == OPS_LINE else mods
                for e in line.events:
                    dest.append(Op(e.name, e.start_ns, e.duration_ns))
            devices.append((plane.name, ops, mods))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    host.append((line.name, e.name, e.start_ns,
                                 e.duration_ns))
    return devices, host


def busy_union(ops: List[Op]) -> Tuple[float, List[Tuple[float, float]]]:
    """Seconds covered by at least one operation, and the idle gaps
    between the first start and the last end."""
    iv = sorted((o.start_ns, o.start_ns + o.dur_ns) for o in ops)
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in iv:
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s > cur_e:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy * 1e-9, gaps


def load_classes(kernels_dir: Path) -> List[dict]:
    classes = []
    for f in sorted(kernels_dir.glob("*.json")):
        c = json.loads(f.read_text())
        c["regex"] = re.compile(c["match"])
        classes.append(c)
    return classes


def classify(op: Op, classes: List[dict]) -> Optional[dict]:
    """The class of a Pallas kernel; ``None`` for an XLA operation.  A
    kernel that no class claims, or that two claim, fails."""
    text = op.name
    if PALLAS not in text:
        return None
    claims = [c for c in classes if c["regex"].search(text)]
    if len(claims) != 1:
        which = ", ".join(c["class"] for c in claims) or "no class"
        raise TraceError(f"a Pallas kernel claimed by {which} in "
                         f"bench/kernels, not by one: {text[:500]}")
    return claims[0]


def reduce_ops(devices, host, window_s: float, classes: List[dict],
               peak: Optional[dict]) -> Reduced:
    """Per chip: busy time, linear time and least time, and each
    operation kind's time are means over the device planes that ran
    anything; idle gaps are those in which none of them ran."""
    planes = [(ops, mods) for _, ops, mods in devices if ops]
    n = max(len(planes), 1)
    t_busy = 0.0
    programs: Dict[str, List[float]] = {}
    per_op: Dict[str, float] = {}
    linear_calls, linear_s, least = 0, 0.0, 0.0
    least_known = peak is not None
    for ops, mods in planes:
        t_busy += busy_union(ops)[0]
        for m in mods:
            programs.setdefault(_program(m.name), []).append(m.dur_ns * 1e-9)
        for o in ops:
            c = classify(o, classes)
            key = c["class"] if c else o.kind
            if key not in CONTAINERS:
                per_op[key] = per_op.get(key, 0.0) + o.dur_ns * 1e-9
            if c is not None and c["class"] == "linear":
                linear_calls += 1
                linear_s += o.dur_ns * 1e-9
                f, by = _linear_counts_cached(o.name)
                if least_known:
                    least += least_time(f, by, peak)
    top = sorted(((k, v / n) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])
    gaps = busy_union([o for ops, _ in planes for o in ops])[1]
    return Reduced(window_s=window_s, busy_s=t_busy / n, programs=programs,
                   linear_calls=linear_calls, linear_s=linear_s / n,
                   linear_least_s=least / n if least_known and linear_calls
                   else None,
                   top_ops=top,
                   idle_gaps=_label_gaps(gaps, host))


_COUNTS: Dict[str, Tuple[float, float]] = {}


def _linear_counts_cached(text: str) -> Tuple[float, float]:
    if text not in _COUNTS:
        _COUNTS[text] = linear_counts(text)
    return _COUNTS[text]


def _label_gaps(gaps, host, keep: int = 10) -> List[Tuple[str, float]]:
    """The longest idle gaps, each named by the innermost host event that
    covers its middle (what the host was doing meanwhile), taken from
    the Python thread where it has one."""
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:keep]
    out = []
    for s, e in longest:
        mid = (s + e) / 2
        found = {}
        for line, name, hs, hd in host:
            if hs <= mid <= hs + hd:
                pri = 0 if line.startswith("python") else 1
                if pri not in found or hd < found[pri][1]:
                    found[pri] = (f"{line}: {name}", hd)
        label = found[min(found)][0] if found else "host: nothing traced"
        out.append((label, (e - s) * 1e-9))
    return out


def reduce_trace(trace_dir: Path, window_s: float, kernels_dir: Path,
                 peak: Optional[dict] = None) -> Reduced:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    devices, host = [], []
    for f in files:
        d, h = load_ops(f)
        devices += d
        host += h
    return reduce_ops(devices, host, window_s, load_classes(kernels_dir),
                      peak)
