#!/usr/bin/env python3
"""Record the small chip trace that tests/bench/test_bench_trace.py reads.

    python3 bench/record_trace_fixture.py --out <dir>

On a TPU: stands up ``internlm2-1_8b-2of4`` at the ``chat_short`` cell's
shapes, warms it up, and traces one ``Engine.run`` of one request (a
32-token prompt, one prefill chunk, then two decode steps).  The
``.xplane.pb`` is copied to ``<dir>/decode_2of4.xplane.pb``, with the
engine's counts beside it in ``decode_2of4.json``.
"""

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
CELL = "internlm2-1_8b-2of4.chat_short"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True, type=Path)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench import arrivals
    from bench.harness import load_cell, use_compile_cache
    from bench.sut import Served

    use_compile_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("record_trace_fixture: no TPU", file=sys.stderr)
        return 3
    cell = load_cell(ROOT, CELL)
    served = Served(cell.reference(), cell.adapter(), 1, cell.config,
                    cell.mix)
    served.run(arrivals.warmup_requests(cell.mix))
    one = [arrivals.Drawn(rid=0, prompt=tuple(range(1, 33)),
                          max_new_tokens=3, arrival=0.0)]
    with tempfile.TemporaryDirectory() as tdir:
        jax.profiler.start_trace(tdir)
        report = served.run(one)
        jax.profiler.stop_trace()
        (xplane,) = Path(tdir).rglob("*.xplane.pb")
        args.out.mkdir(parents=True, exist_ok=True)
        shutil.copy(xplane, args.out / "decode_2of4.xplane.pb")
    (args.out / "decode_2of4.json").write_text(json.dumps({
        "cell": CELL, "device_kind": jax.devices()[0].device_kind,
        "prefill_chunks": report.prefill_chunks,
        "decode_calls": report.decode_calls,
        "generated_tokens": report.generated_tokens}, indent=1) + "\n")
    print(f"recorded {args.out / 'decode_2of4.xplane.pb'}: "
          f"{report.describe()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
