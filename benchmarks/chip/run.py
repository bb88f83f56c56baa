#!/usr/bin/env python3
"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from ``peaks.json``.
The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``; ``checks`` last: each number compared with its
limit).  The checks are also the last lines of standard error.
``--control 1`` judges the correctness control in the program's place
(the reference in the next lower precision, under the same limits), so
``correct`` has to read false; the benchmark's own runs leave it off.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    cell = harness.load_cell(args.workload)
    device = harness.require_device(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    # cache every program, however quick to compile: set-up then repeats
    # the same cache reads on every run instead of recompiling small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = harness.run(
        cell, args.seed, args.seconds, bool(args.trace), T_START, device,
        control=bool(args.control),
        log=lambda s: print(s, file=sys.stderr, flush=True))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
