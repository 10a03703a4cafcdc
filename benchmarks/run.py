"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Sections:
  table4    ML model error rates (paper Table IV)
  fig6      static workloads, default/CARAT/optimal (paper Fig 6)
  fig7      dynamic workload sequences (paper Fig 7)
  table5    independent per-client tuning (paper Table V)
  table6    external interference (paper Table VI)
  fig8      DLIO DL kernels (paper Fig 8)
  table7    h5bench HPC kernels (paper Table VII)
  table8    per-client overheads (paper Table VIII)
  ablation  tuner strategy ablation (paper §III-D, quantified)
  ablation_tau  tau sweep measuring the GBDT calibration gap
  roofline  per-(arch x shape x mesh) dry-run roofline terms (§Roofline)
  sharded   sharded runtime gates (sync identity + async stragglers,
            process-mode replay identity, kill+restore-from-snapshot)
  soa_device  device-resident soa-jax fleet gates (fused step speedup,
            million-client interval, shard->device sync equivalence)
  transport cross-process transport gates (spawned-fleet pipe/socket
            identity, elastic repartition, async process stragglers)
  telemetry telemetry on/off overhead gate (bit-identity + wall-clock
            envelope; span/counter micro-costs)

Tooling sections (repo gates, not paper artifacts):
  lint      caratlint contract pass over src/tests/benchmarks
            (hard-fails on findings; catalogue in CONTRIBUTING.md)

Run a subset with ``python -m benchmarks.run --only fig6,table8``;
``--list`` prints the section names.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from benchmarks import (
    bench_model_accuracy,
    bench_static,
    bench_dynamic,
    bench_independent,
    bench_interference,
    bench_dlio,
    bench_h5,
    bench_overhead,
    bench_tuner_ablation,
    bench_roofline,
    bench_sharded,
    bench_soa_device,
    bench_transport,
)

def run_lint() -> None:
    """Tooling gate: the caratlint contract pass (CONTRIBUTING.md)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.caratlint.baseline import DEFAULT_BASELINE, load_baseline
    from tools.caratlint.engine import lint_paths

    result = lint_paths(["src", "tests", "benchmarks"], root=repo,
                        baseline=load_baseline(DEFAULT_BASELINE))
    for f in result.findings:
        print(f"# {f.render()}", file=sys.stderr)
    print(f"caratlint,0,findings={len(result.findings)}"
          f";files={result.files_scanned}")
    if result.findings:
        raise RuntimeError(
            f"caratlint: {len(result.findings)} contract finding(s) — "
            f"run `python -m tools.caratlint` for details")


SECTIONS = [
    ("table4", bench_model_accuracy.run),
    ("fig6", bench_static.run),
    ("fig7", bench_dynamic.run),
    ("table5", bench_independent.run),
    ("table6", bench_interference.run),
    ("fig8", bench_dlio.run),
    ("table7", bench_h5.run),
    ("table8", bench_overhead.run),
    ("ablation", bench_tuner_ablation.run),
    ("ablation_tau", bench_tuner_ablation.run_tau_sweep),
    ("roofline", bench_roofline.run),
    ("sharded", bench_sharded.run),
    ("soa_device", bench_soa_device.run),
    ("transport", bench_transport.run),
    ("telemetry", bench_overhead.run_telemetry),
    # tooling sections: repo gates that ride the same harness
    ("lint", run_lint),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated section names")
    ap.add_argument("--list", action="store_true",
                    help="print section names and exit")
    args = ap.parse_args()
    if args.list:
        for name, _ in SECTIONS:
            print(name)
        return
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")
    failures = []
    for name, fn in SECTIONS:
        if only is not None and name not in only:
            continue
        t0 = time.time()
        try:
            fn()
        except Exception as e:
            failures.append((name, repr(e)))
            traceback.print_exc()
        print(f"# section {name} done in {time.time()-t0:.1f}s",
              file=sys.stderr)
    if failures:
        print(f"# {len(failures)} section failures: {failures}",
              file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
