#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; ``bench/harness.py`` says how a run goes.  ``--trace 0``
prints the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics read from a profiler trace of the window's first segment.  The
last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its
limit); the same checks are the last lines of standard error.

``--control int8`` serves the program's own int8 weight path in place
of the configuration's precision: the control that ``correct`` must
fail.  The benchmark's runs never pass it.

Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8",), default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench.harness import (BenchError, load_cell, print_result,
                               run_cell, use_compile_cache)

    try:
        cell = load_cell(ROOT, args.workload)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2

    import jax

    use_compile_cache(ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU: JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t0=T0, control=args.control)
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
