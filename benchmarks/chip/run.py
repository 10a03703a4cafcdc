#!/usr/bin/env python3
"""On-chip benchmark of the CARAT fleet: one cell, one seed, one window.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs the chips the cell asks
for: without a TPU, with too few chips, or on a ``device_kind`` missing
from ``peaks.json`` it exits nonzero and prints no result. It loads the
cell named in ``BENCHMARK.json``, warms every shape up (set-up), measures
for ``--seconds``, checks what the timed path produced against the plain
references, and prints one JSON object as its last stdout line. With
``--trace 1`` the metrics are the cell's per-layer metrics, read from the
profiler's trace and the program's spans; with ``--trace 0`` they are
its end-to-end metrics. JAX's compile cache is the checkout's
``.cache/jax`` (or ``JAX_COMPILATION_CACHE_DIR``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from chipbench import harness, spec
    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: no program under {ROOT}/src", file=sys.stderr)
        return 2
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    except spec.SpecError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
