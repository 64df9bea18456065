"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload uvit_h8.r32.b32 --seed 7 --seconds 10 \
        --trace 0

Run from the root of a checkout.  The cell, its configuration and its
metrics are found by name (``BENCHMARK.json``, ``bench/workloads/``,
``bench/configs/``, ``bench/metrics/``).  The last line of standard output
is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (``--trace 1``: also ``breakdown``) and, last,
``compared``: each number of the comparison with the plain reference
beside its limit.  The same numbers are the last lines of standard error.

The run needs a TPU with as many chips as the cell names.  On any other
platform, or with fewer chips, it exits 3 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), t0=T0)
    except harness.NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
