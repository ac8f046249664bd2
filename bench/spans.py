"""Engine spans in a profiler trace: what the host loop was doing while
the device idled.

``repro.serving.Engine.run`` marks each phase of its loop with a
``jax.profiler.TraceAnnotation`` named ``engine.*`` (``engine.run``,
``engine.iter``, ``engine.admit``, ``engine.prefill``,
``engine.decode_feed``, ``engine.dispatch``, ``engine.sync``,
``engine.retire``).  They land on the host plane, on the thread line of
the Python interpreter, on the same clock as the device's operations;
``trace.load_ops`` returns them with the other host events.  From them:

- nesting: on one thread line a span's parent is the innermost span
  that contains it; its self time is its duration less its children's;
- idle by span: every idle nanosecond of a device in the traced window
  (up to the last engine span's end) goes to the innermost
  engine span that covers it, by overlap; idle that no engine span
  covers goes to ``outside engine spans``.  The busy and idle intervals
  are ``trace.busy_union``'s, with the edges of the window added; with
  several device planes the seconds are their mean, as busy time is.

    python3 -m bench.spans <trace dir or .xplane.pb>

prints the reduction of a trace as one JSON object.
"""

from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from .trace import busy_union, load_ops

PREFIX = "engine."
OUTSIDE = "outside engine spans"
SYNC = "engine.sync"
ITER = "engine.iter"


@dataclasses.dataclass
class Span:
    name: str
    line: str
    start_ns: float
    end_ns: float
    depth: int = 0
    parent: Optional["Span"] = None
    child_ns: float = 0.0

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    @property
    def self_ns(self) -> float:
        return self.dur_ns - self.child_ns


@dataclasses.dataclass
class Spans:
    spans: List[Span]
    window_s: float                      # up to the last engine span
    idle_by_span: List[Tuple[str, float]]   # seconds, longest first

    @property
    def idle_s(self) -> float:
        """Device idle in the window, all of it put to some span."""
        return sum(sec for _, sec in self.idle_by_span)

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def self_s(self) -> Dict[str, float]:
        """Self time of each span name, in seconds, longest first."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.self_ns * 1e-9
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))

    def host_loop_ms(self) -> Optional[float]:
        """Mean over ``engine.iter`` spans of their duration less the
        ``engine.sync`` spans inside them: the host's own time per
        iteration."""
        iters = self.named(ITER)
        if not iters:
            return None
        synced: Dict[int, float] = {}
        for s in self.named(SYNC):
            p = s.parent
            while p is not None and p.name != ITER:
                p = p.parent
            if p is not None:
                synced[id(p)] = synced.get(id(p), 0.0) + s.dur_ns
        own = [it.dur_ns - synced.get(id(it), 0.0) for it in iters]
        return 1e-6 * sum(own) / len(own)

    def engine_idle_s(self) -> float:
        """Device idle inside an engine span other than ``engine.sync``:
        the idle that the host loop's own work leaves."""
        return sum(sec for name, sec in self.idle_by_span
                   if name not in (SYNC, OUTSIDE))

    def summary(self) -> dict:
        return {"window_s": self.window_s, "idle_s": self.idle_s,
                "iterations": len(self.named(ITER)),
                "host_loop_ms": self.host_loop_ms(),
                "engine_idle_s": self.engine_idle_s(),
                "idle_by_span": [[n, s] for n, s in self.idle_by_span],
                "self_s": self.self_s()}


def nest(host) -> List[Span]:
    """The ``engine.*`` events of ``host`` (``load_ops``' host list) as
    spans, each line's nested by containment, in start order."""
    by_line: Dict[str, List[Span]] = {}
    for line, name, start, dur in host:
        if name.startswith(PREFIX):
            by_line.setdefault(line, []).append(
                Span(name, line, start, start + dur))
    out = []
    for spans in by_line.values():
        spans.sort(key=lambda s: (s.start_ns, -s.end_ns))
        stack: List[Span] = []
        for s in spans:
            while stack and stack[-1].end_ns <= s.start_ns:
                stack.pop()
            if stack and s.end_ns <= stack[-1].end_ns:
                s.parent = stack[-1]
                s.depth = s.parent.depth + 1
                s.parent.child_ns += s.dur_ns
            else:
                stack.clear()   # overlaps without nesting: a new root
            stack.append(s)
        out += spans
    out.sort(key=lambda s: (s.start_ns, -s.end_ns))
    return out


def innermost_segments(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """The timeline as ``(start, end, name)`` pieces, each named by the
    innermost span over it (the deepest; of equals, the shortest)."""
    spans = [s for s in spans if s.dur_ns > 0]
    events = sorted([(s.start_ns, 1, i) for i, s in enumerate(spans)]
                    + [(s.end_ns, 0, i) for i, s in enumerate(spans)])
    active: Dict[int, Span] = {}
    segs: List[Tuple[float, float, str]] = []
    k = 0
    while k < len(events):
        t = events[k][0]
        while k < len(events) and events[k][0] == t:
            _, opening, i = events[k]
            if opening:
                active[i] = spans[i]
            else:
                active.pop(i, None)
            k += 1
        if k < len(events) and active:
            inner = max(active.values(), key=lambda s: (s.depth, -s.dur_ns))
            segs.append((t, events[k][0], inner.name))
    return segs


def attribute(idle: List[Tuple[float, float]],
              segs: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds of each idle interval by the segment that overlaps
    them; the rest to ``OUTSIDE``."""
    starts = [s for s, _, _ in segs]
    out: Dict[str, float] = {}
    for gs, ge in idle:
        left = ge - gs
        k = max(bisect.bisect_right(starts, gs) - 1, 0)
        while k < len(segs) and segs[k][0] < ge:
            s, e, name = segs[k]
            ov = min(e, ge) - max(s, gs)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                left -= ov
            k += 1
        if left > 0:
            out[OUTSIDE] = out.get(OUTSIDE, 0.0) + left
    return out


def idle_intervals(ops, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Where no operation ran between ``lo`` and ``hi``: the gaps of
    ``busy_union`` and the stretches before the first operation and
    after the last, each clipped to the window."""
    if not ops:
        return [(lo, hi)]
    _, gaps = busy_union(ops)
    first = min(o.start_ns for o in ops)
    last = max(o.start_ns + o.dur_ns for o in ops)
    out = []
    for s, e in [(lo, first)] + gaps + [(last, hi)]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def reduce_spans(devices, host,
                 window_s: Optional[float] = None) -> Optional[Spans]:
    """The span reduction of ``load_ops``' output; ``None`` when the
    trace holds no engine span (a program without them).  The window
    ends with the last engine span; it starts with the first, or
    ``window_s`` before its end where given: the harness's traced window
    is timed on the host from before the profiler starts, and that
    stretch is idle outside every span."""
    spans = nest(host)
    if not spans:
        return None
    hi = max(s.end_ns for s in spans)
    lo = (min(s.start_ns for s in spans) if window_s is None
          else hi - window_s * 1e9)
    segs = innermost_segments(spans)
    total: Dict[str, float] = {}
    # a trace in which no device ran anything was idle throughout
    planes = [ops for _, ops, _ in devices if ops] or [[]]
    for ops in planes:
        for name, ns in attribute(idle_intervals(ops, lo, hi), segs).items():
            total[name] = total.get(name, 0.0) + ns
    n = len(planes)
    by_span = sorted(((name, ns * 1e-9 / n) for name, ns in total.items()),
                     key=lambda kv: -kv[1])
    return Spans(spans=spans, window_s=(hi - lo) * 1e-9,
                 idle_by_span=by_span)


def reduce_path(path: Path,
                window_s: Optional[float] = None) -> Optional[Spans]:
    """The span reduction of one ``.xplane.pb``, or of every one under a
    directory."""
    path = Path(path)
    files = [path] if path.is_file() else sorted(path.rglob("*.xplane.pb"))
    devices, host = [], []
    for f in files:
        d, h = load_ops(f)
        devices += d
        host += h
    return reduce_spans(devices, host, window_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", type=Path)
    args = ap.parse_args(argv)
    red = reduce_path(args.trace)
    if red is None:
        print(f"bench.spans: no engine span in {args.trace}",
              file=sys.stderr)
        return 1
    print(json.dumps(red.summary()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
