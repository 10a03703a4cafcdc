#!/usr/bin/env python3
"""Record the small profiler trace that the self-tests reduce.

Runs on one TPU: a 64-client striped fleet on 16 OSTs with CARAT
attached, warmed up, then two intervals traced with the benchmark's own
``bench.interval`` annotations (so the trace holds the fused step and
the GBDT kernel). Writes ``testdata/small_carat.xplane.pb`` beside this
file, or to ``--out``.

    python3 benchmarks/chip/record_testdata.py [--out PATH]
"""
import argparse
import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(HERE, "testdata",
                                                  "small_carat.xplane.pb"))
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_testdata: JAX found no TPU", file=sys.stderr)
        return 1
    from repro.core.ml.train import load_gbdt
    from repro.core.policies.carat import CaratPolicy
    from repro.core.policy import default_spaces
    from repro.storage import Simulation
    from repro.storage.params import PFSParams
    from repro.storage.workloads import striped_fleet
    models = {op: load_gbdt(os.path.join(HERE, "models", f"gbdt_{op}.npz"))
              for op in ("read", "write")}
    sim = Simulation(striped_fleet(64), params=PFSParams(n_osts=16), seed=1,
                     backend="soa-jax")
    sim.attach_policy(CaratPolicy(default_spaces(), models))
    for _ in range(8):
        sim.step()
    d = tempfile.mkdtemp(prefix="chipbench_testdata_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.interval"):
            sim.step()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    shutil.copy(path, args.out)
    shutil.rmtree(d, ignore_errors=True)
    print(f"record_testdata: {os.path.getsize(args.out)} bytes -> {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
