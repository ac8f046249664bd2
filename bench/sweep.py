#!/usr/bin/env python3
"""Read the numbers ``correct`` compares over many seeds in one process.

    python3 bench/sweep.py --workload <cell> --seeds 11,12,13 \
        [--control int8] [--seconds 0]

Each seed is a whole run of the cell (weights from the seed, the window,
the comparison with the reference), as ``bench/run.py`` makes it, but
set-up after the first seed finds every program already compiled in the
process.  ``--seconds 0`` measures one segment, which finishes every
request the mix holds.  One JSON line per seed: the seed, the control,
and each number compared.  This is how a cell's limits are read
(``bench/limits/<cell>.json``); the benchmark's own runs do not call it.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", choices=("int8",), default=None)
    ap.add_argument("--seconds", type=float, default=0.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench.harness import load_cell, run_cell, use_compile_cache

    cell = load_cell(ROOT, args.workload)
    use_compile_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = run_cell(cell, seed, args.seconds, False, t0=t0,
                       control=args.control,
                       log=lambda *a: print(*a, file=sys.stderr, flush=True))
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": res["correct"],
                          "failed": res["failed"],
                          "numbers": {k: c["value"] for k, c in
                                      res["checks"].items()},
                          "seconds": round(time.perf_counter() - t0, 1)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
