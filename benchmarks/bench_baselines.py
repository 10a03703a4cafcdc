"""Head-to-head tuning-policy comparison over the bundled replay corpus.

The paper's headline claim (up to 3x over default/static configs) only
means something against real competitors. This benchmark runs every
registered :class:`TuningPolicy` on the *same* simulator, the same
bundled traces, and the same seed:

* ``static`` — the Lustre default config, never adapted (the floor);
* ``carat``  — the paper's two-stage co-tuner (pretrained GBDT pair);
* ``dial``   — DIAL-style decentralized learned clients (online
  neighbourhood bandits over locally observable metrics, no pretraining);
* ``magpie`` — Magpie-style centralized tabular DRL actor emitting one
  fleet-wide action.

Gates:

1. **Coverage** (hard): all four policies complete all three bundled
   traces and report aggregate throughput.
2. **CARAT >= static default** (hard): CARAT's corpus-aggregate
   throughput is at least the static default's — an adaptive tuner that
   loses to never-tuning has regressed.
3. **Determinism** (hard): rerunning the learned baselines (dial,
   magpie) on one trace reproduces their decision logs exactly — the
   online learners must draw from their own RngStreams only.

Emitted rows (benchmarks/common.py CSV convention) plus a
``BENCH_baselines.json`` artifact with the raw numbers.

Usage:
    PYTHONPATH=src python benchmarks/bench_baselines.py [--smoke]
"""
import argparse
import json
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, "benchmarks")

from common import carat_models, emit  # noqa: E402

from repro.core import default_spaces, make_policy, policy_from_config  # noqa: E402
from repro.storage import (bundled_traces, compile_trace,  # noqa: E402
                           load_bundled_trace, simulation_from_schedules)

SPACES = default_spaces()
POLICY_NAMES = ("static", "carat", "dial", "magpie")


def build_policy(name: str):
    """Per-policy construction via the registry (what a user would do)."""
    if name == "carat":
        # backend="numpy" is the bit-exact scoring path (what "auto"
        # resolves to on CPU hosts)
        return make_policy("carat", spaces=SPACES, models=carat_models(),
                           backend="numpy")
    if name == "static":
        return make_policy("static")        # Lustre default config
    return make_policy(name, spaces=SPACES)  # dial / magpie


def _decision_count(policy) -> int:
    d = getattr(policy, "decisions", [])
    if d and isinstance(d[0], list):
        return sum(len(x) for x in d)
    return len(d)


def run_policy(name: str, schedules, seed: int = 7):
    """(aggregate_bytes_per_s, n_decisions, wall_s, decision_log)."""
    duration = max(s.duration for s in schedules.values())
    sim = simulation_from_schedules(schedules, seed=seed)
    policy = sim.attach_policy(build_policy(name))
    t0 = time.perf_counter()
    res = sim.run(duration)
    wall = time.perf_counter() - t0
    log = getattr(policy, "decisions", [])
    return res.aggregate_throughput, _decision_count(policy), wall, log


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: identical gates, relaxed wall-clock "
                         "expectations on noisy 2-CPU runners")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    failures = []
    report = {"smoke": bool(args.smoke), "seed": args.seed, "traces": {},
              "corpus": {}}

    # registry round-trip smoke: every policy's config() reconstructs
    for name in POLICY_NAMES:
        p = build_policy(name)
        if type(policy_from_config(p.config())) is not type(p):
            failures.append(f"{name}: config() does not round-trip")

    corpus = {name: compile_trace(load_bundled_trace(name))
              for name in bundled_traces()}
    totals = {name: 0.0 for name in POLICY_NAMES}
    for trace_name, schedules in corpus.items():
        row = {}
        for name in POLICY_NAMES:
            agg, n_dec, wall, _ = run_policy(name, schedules,
                                             seed=args.seed)
            totals[name] += agg
            row[name] = {"aggregate_mbps": agg / 1e6, "decisions": n_dec,
                         "wall_s": wall}
            emit(f"baselines/{trace_name}/{name}", wall * 1e6,
                 f"{agg/1e6:.1f}MBps|{n_dec}dec")
        base = row["static"]["aggregate_mbps"]
        for name in POLICY_NAMES:
            row[name]["over_static"] = row[name]["aggregate_mbps"] \
                / max(base, 1e-9)
        report["traces"][trace_name] = row

    report["corpus"] = {name: totals[name] / 1e6 for name in POLICY_NAMES}
    gain = totals["carat"] / max(totals["static"], 1e-9)
    report["carat_over_static"] = gain
    emit("baselines/corpus/carat_over_static", 0.0, f"{gain:.3f}x")
    if totals["carat"] < totals["static"]:
        failures.append(f"CARAT corpus aggregate is below the static "
                        f"default ({gain:.3f}x < 1.0)")

    # determinism of the learned baselines: same seed -> same decisions
    trace0 = bundled_traces()[0]
    for name in ("dial", "magpie"):
        _, _, _, log_a = run_policy(name, corpus[trace0], seed=args.seed)
        _, _, _, log_b = run_policy(name, corpus[trace0], seed=args.seed)
        if log_a != log_b:
            failures.append(f"{name}: decision log is not deterministic "
                            f"across reruns on {trace0}")

    report["failures"] = failures
    with open("BENCH_baselines.json", "w") as f:
        json.dump(report, f, indent=2)

    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
