#!/usr/bin/env python3
"""Readings that the correctness limits are set from, for one cell.

For each seed, one process runs the cell through the harness with a
short window (the samples it compares are the same as a full run's), and
reads every compared number twice: from the program, and from the
control, which puts the reference computed one precision lower in the
program's place (float32 for the float64 fleet step, bfloat16 for the
float32 GBDT kernel), both through the references that the cell's
configuration names. The limits in ``limits/<cell>.json`` lie between
the program's largest reading and the control's smallest. The
benchmark's own runs never run the control.

    python3 benchmarks/chip/readings.py --workload <cell> \
        --seeds 11 12 ... [--seconds 6] [--out readings.json]
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def control_numbers(keep: dict, carat: bool) -> dict:
    from chipbench import check
    out = check.fleet_numbers(keep["ref"], keep["samples"],
                              dtype=np.float32, use_program=False)
    if carat:
        out.update(check.tuner_numbers(keep["ref"], keep["samples"],
                                       use_program=False))
    return {k: v for k, v in out.items() if not k.startswith("_")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from chipbench import harness, spec
    cell = spec.load_cell(args.workload)
    rows = []
    for seed in args.seeds:
        keep: dict = {}
        res = harness.run(cell, seed, args.seconds, False,
                          time.perf_counter(), keep=keep)
        prog = {k: v for k, v in keep["numbers"].items()
                if not k.startswith("_")}
        ctrl = control_numbers(keep, cell.is_carat)
        counts = {k[1:]: v for k, v in keep["numbers"].items()
                  if k.startswith("_") and k != "_fleet_worst_field"}
        rows.append({"seed": seed, "program": prog, "control": ctrl,
                     "correct": res["correct"],
                     "worst_field": keep["numbers"].get("_fleet_worst_field"),
                     "samples": len(keep["samples"]), "compared": counts})
        print(json.dumps(rows[-1]), flush=True)
        del keep, res
        gc.collect()            # frees the last fleet's device buffers
    names = rows[0]["program"].keys()
    summary = {n: {"program_max": max(r["program"][n] for r in rows),
                   "control_min": min(r["control"][n] for r in rows)}
               for n in names}
    out = {"workload": args.workload, "seeds": args.seeds, "rows": rows,
           "summary": summary}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
