"""Trace-driven replay sweep: the fleet controller vs static baselines on a
multi-phase replayed trace (the paper's Fig 7 dynamic-pattern regime, run
from a trace instead of hand-scripted switches).

Gates:

1. **Parse determinism** (hard): every bundled trace parses to the same
   Trace twice, render->parse round-trips exactly, and compilation
   produces the identical phase schedule both times (plus synthetic-trace
   round-trips across seeds).
2. **Phase-switch decision identity** (hard): replaying the strided
   MPI-IO trace, per-client CARAT controllers and the fleet-batched
   engine make bit-identical decisions (RPC decisions, cache limits,
   end-to-end bytes) — workload switches must not desynchronize the
   batched path.
3. **Adaptivity** (gated): on the ``mixed_shift`` trace the fleet
   controller beats the static-default aggregate and, within each
   replayed phase, approaches that phase's best static candidate
   (median ratio floor; candidates are the known per-regime optima).
4. **Parse throughput** (generous floor): records/s over the bundled
   corpus — a regression canary, not a performance claim.

Emitted rows (benchmarks/common.py CSV convention) plus a
``BENCH_replay.json`` artifact with the raw numbers.

Usage:
    PYTHONPATH=src python benchmarks/bench_replay.py [--smoke]
"""
import argparse
import json
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, "benchmarks")

import numpy as np  # noqa: E402

from common import carat_models, emit  # noqa: E402

from repro.config.types import CaratConfig  # noqa: E402
from repro.core import (CaratController, CaratPolicy,  # noqa: E402
                        NodeCacheArbiter, PerClientPolicy, default_spaces)
from repro.storage import (ClientConfig, bundled_traces, compile_trace,  # noqa: E402
                           load_bundled_trace, parse_trace, render_trace,
                           simulation_from_schedules, synthesize_trace)

SPACES = default_spaces()

# per-regime static optima candidates (paper Table V mechanisms): default,
# small-random window, deep seq pipeline, small+deep, big-write, tiny cache
CANDIDATES = (
    ("default", ClientConfig(1024, 8, 2048)),
    ("w16_f8", ClientConfig(16, 8, 2048)),
    ("w64_f256", ClientConfig(64, 256, 2048)),
    ("w16_f64", ClientConfig(16, 64, 2048)),
    ("w1024_f64", ClientConfig(1024, 64, 2048)),
    ("w256_f64_c64", ClientConfig(256, 64, 64)),
)


def _copy_cfg(cfg):
    return ClientConfig(cfg.rpc_window_pages, cfg.rpcs_in_flight,
                        cfg.dirty_cache_mb)


# ------------------------------------------------------------ gate 1 + 4 --
def parse_determinism(n_synth=8):
    """(all_deterministic, records_parsed, parse_seconds)."""
    ok = True
    n_records = 0
    t0 = time.perf_counter()
    for name in bundled_traces():
        t1, t2 = load_bundled_trace(name), load_bundled_trace(name)
        rt = parse_trace(render_trace(t1), name=name)
        ok &= (t1 == t2 == rt)
        ok &= (compile_trace(t1) == compile_trace(t2))
        n_records += t1.n_records
    for seed in range(n_synth):
        t = synthesize_trace(seed, n_clients=3, duration_s=60.0)
        ok &= (parse_trace(render_trace(t), name=t.name) == t)
        ok &= (compile_trace(t) == compile_trace(t))
        n_records += t.n_records
    return ok, n_records, time.perf_counter() - t0


# --------------------------------------------------------------- gate 2 --
def decision_identity(seed=3):
    """Per-client controllers vs the fleet engine on a replayed
    multi-client trace: identical decisions, cache limits, bytes."""
    schedules = compile_trace(load_bundled_trace("mpiio_strided_ckpt"))
    duration = max(s.duration for s in schedules.values())
    cfg = CaratConfig()

    sim_a = simulation_from_schedules(schedules, seed=seed)
    percl = []
    for cid in sorted(schedules):
        ctrl = CaratController(cid, SPACES, carat_models(), cfg,
                               arbiter=NodeCacheArbiter(SPACES))
        percl.append(ctrl)
    sim_a.attach_policy(PerClientPolicy({c.client_id: c for c in percl}))
    res_a = sim_a.run(duration)

    sim_b = simulation_from_schedules(schedules, seed=seed)
    fleet = sim_b.attach_policy(CaratPolicy(SPACES, carat_models(), cfg=cfg,
                                            backend="numpy"))
    res_b = sim_b.run(duration)

    identical = all(a.decisions == b.decisions
                    for a, b in zip(percl, fleet.controllers))
    identical &= ([c.config.dirty_cache_mb for c in sim_a.clients]
                  == [c.config.dirty_cache_mb for c in sim_b.clients])
    identical &= (res_a.app_read_bytes == res_b.app_read_bytes
                  and res_a.app_write_bytes == res_b.app_write_bytes)
    n_dec = sum(len(c.decisions) for c in percl)
    return identical, n_dec, fleet.boundary_count


# --------------------------------------------------------------- gate 3 --
def _phase_windows(schedule, interval_s):
    """(label, i0, i1) interval-index windows of the active phases."""
    out = []
    for p in schedule.active_phases():
        i0 = int(round(p.start_s / interval_s))
        i1 = int(round(p.end_s / interval_s))
        out.append((p.spec.name.split(":")[-1], i0, i1))
    return out


def adaptivity(seed=7, interval_s=0.5):
    schedules = compile_trace(load_bundled_trace("mixed_shift"))
    sched = schedules[0]
    duration = sched.duration
    windows = _phase_windows(sched, interval_s)

    def replay_static(cfg):
        sim = simulation_from_schedules(schedules, configs=[_copy_cfg(cfg)],
                                        seed=seed, interval_s=interval_s)
        return sim.run(duration)

    static = {name: replay_static(cfg) for name, cfg in CANDIDATES}

    sim = simulation_from_schedules(schedules, seed=seed,
                                    interval_s=interval_s)
    fleet = sim.attach_policy(CaratPolicy(SPACES, carat_models(),
                                          backend="numpy"))
    res_c = sim.run(duration)

    def phase_thr(res, i0, i1):
        return float(np.mean(res.client_throughput[0][i0:i1]))

    phases = []
    for label, i0, i1 in windows:
        carat_p = phase_thr(res_c, i0, i1)
        best_name, best_p = max(
            ((n, phase_thr(r, i0, i1)) for n, r in static.items()),
            key=lambda kv: kv[1])
        phases.append(dict(phase=label, carat=carat_p, static_best=best_p,
                           static_best_cfg=best_name,
                           default=phase_thr(static["default"], i0, i1),
                           ratio_vs_best=carat_p / max(best_p, 1.0)))
    agg = dict(
        carat=res_c.aggregate_throughput,
        default=static["default"].aggregate_throughput,
        static_best=max(r.aggregate_throughput for r in static.values()),
        static_best_cfg=max(static, key=lambda n:
                            static[n].aggregate_throughput),
    )
    return phases, agg, fleet


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="relaxed adaptivity/timing floors for noisy "
                         "2-CPU CI runners")
    args = ap.parse_args(argv)

    # gates scale with runner noise, not trace size: the replay itself is
    # deterministic, only the throughput ratios move with the trained model
    agg_floor = 1.02 if args.smoke else 1.05
    phase_floor = 0.60 if args.smoke else 0.70
    # records/s canary for catastrophic parser regressions only — the
    # corpus is small, so fixed overheads + runner contention dominate
    rate_floor = 100.0 if args.smoke else 300.0

    failures = []
    report = {"smoke": bool(args.smoke)}

    # -- 1. deterministic parsing + 4. parse throughput ----------------------
    ok, n_records, secs = parse_determinism()
    rate = n_records / max(secs, 1e-9)
    report["parse_deterministic"] = ok
    report["parse_records_per_s"] = rate
    emit("replay_parse", secs / max(n_records, 1) * 1e6,
         f"{rate:.0f}rec/s|deterministic={ok}")
    if not ok:
        failures.append("trace parsing/compilation is not deterministic")
    if rate < rate_floor:
        failures.append(f"parse rate {rate:.0f} rec/s < {rate_floor:.0f} "
                        f"floor")

    # -- 2. per-client vs fleet decision identity ----------------------------
    identical, n_dec, n_boundaries = decision_identity()
    report["decisions"] = n_dec
    report["stage2_boundaries"] = n_boundaries
    report["decision_identical"] = identical
    emit("replay_decision_identity", 0.0,
         f"{n_dec}dec|{n_boundaries}boundaries|identical={identical}")
    if not identical:
        failures.append("fleet decisions diverged from the per-client path "
                        "across replayed phase switches")
    if n_boundaries == 0:
        failures.append("replayed trace fired no stage-2 boundaries — the "
                        "gap phases are not arming the boundary machine")

    # -- 3. adaptivity vs static baselines -----------------------------------
    t0 = time.perf_counter()
    phases, agg, fleet = adaptivity()
    us = (time.perf_counter() - t0) * 1e6
    ratios = [p["ratio_vs_best"] for p in phases]
    med_ratio = float(np.median(ratios))
    gain = agg["carat"] / max(agg["default"], 1.0)
    report["phases"] = phases
    report["aggregate"] = agg
    report["median_phase_ratio_vs_best"] = med_ratio
    report["min_phase_ratio_vs_best"] = float(min(ratios))
    report["carat_over_default"] = gain
    for p in phases:
        emit(f"replay_phase/{p['phase']}", us / len(phases),
             f"{p['ratio_vs_best']:.2f}x_best|best={p['static_best_cfg']}")
    emit("replay_aggregate", us,
         f"{gain:.2f}x_default|{med_ratio:.2f}med_vs_best")
    if gain < agg_floor:
        failures.append(f"fleet aggregate is only {gain:.2f}x the static "
                        f"default (< {agg_floor}x floor)")
    if med_ratio < phase_floor:
        failures.append(f"median within-phase ratio vs static-best "
                        f"{med_ratio:.2f} < {phase_floor} floor")

    report["failures"] = failures
    with open("BENCH_replay.json", "w") as f:
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
