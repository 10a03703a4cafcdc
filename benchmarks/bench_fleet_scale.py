"""Fleet-scale sweep: per-client controller loop vs batched fleet engine.

For each fleet size n the same simulation (same workload mix, same seed,
same controller shells) runs twice: once with n independent per-client
``CaratController`` callbacks (hosted by ``PerClientPolicy``), once with
one ``CaratPolicy`` batching every probe's stage-1 tuning into a single
vectorized inference call.

Reported per size:

* per-decision tuner cost of both paths (us) and the speedup;
* whether the fleet's decisions are **bit-identical** to the per-client
  path on the full trace (they must be — the batched path is a compute
  reshape, not an approximation).

The struct-of-arrays gates (ISSUE 6) ride the same entry point:

* scalar <-> SoA identity on the bundled replay corpus with a CARAT
  policy attached — decisions, cumulative counters, and throughput
  series must be bit-identical (hard);
* per-interval step speedup at 4096 clients — the SoA backend must be
  >= 20x faster than the scalar oracle (hard, both modes);
* a 100k-client SoA smoke run must complete (hard).

Emitted rows (benchmarks/common.py CSV convention):
    fleet_scale_percl_n{n},us_per_decision,decisions
    fleet_scale_fleet_n{n},us_per_decision,speedup|identical
    fleet_scale_soa_replay,0,identical
    fleet_scale_soa_step_n4096,ms_per_step,speedup|identical
    fleet_scale_soa_step_n100000,ms_per_step,bytes

Raw numbers land in ``BENCH_fleet_scale.json``.

Usage:
    PYTHONPATH=src python benchmarks/bench_fleet_scale.py [--smoke]

``--smoke`` bounds the decision sweep for CI (<= 64 clients, shorter
sim); the SoA gates always run at full width (4096 / 100k clients).
"""
import argparse
import json
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, "benchmarks")

from common import carat_models, emit  # noqa: E402

from repro.config.types import CaratConfig  # noqa: E402
from repro.core import (CaratController, CaratPolicy,  # noqa: E402
                        NodeCacheArbiter, PerClientPolicy, default_spaces)
from repro.core.ml.train import get_default_models  # noqa: E402
from repro.storage import (Simulation, bundled_traces,  # noqa: E402
                           get_workload, load_bundled_trace,
                           simulation_from_trace)
from repro.storage.soa import OP_FIELDS  # noqa: E402

WL_CYCLE = ("s_rd_rn_8k", "s_wr_sq_1m", "s_rd_sq_1m", "s_wr_rn_8k")


def _workloads(n):
    return [get_workload(WL_CYCLE[i % len(WL_CYCLE)]) for i in range(n)]


def _controllers(n, spaces, models, cfg):
    return [CaratController(i, spaces, models, cfg,
                            arbiter=NodeCacheArbiter(spaces))
            for i in range(n)]


def run_pair(n, duration_s, seed=0, tuner="conditional_score",
             backend="auto"):
    """Run per-client and fleet variants of the same deployment."""
    spaces = default_spaces()
    cfg = CaratConfig(tuner=tuner)
    m_r, m_w = get_default_models()
    gbdts = {"read": m_r, "write": m_w}

    sim_a = Simulation(_workloads(n), seed=seed)
    percl = _controllers(n, spaces, carat_models(), cfg)
    sim_a.attach_policy(PerClientPolicy({c.client_id: c for c in percl}))
    sim_a.run(duration_s)
    n_dec = sum(c.tuner.tune_count for c in percl)
    us_percl = (sum(c.tuner.tune_time_total for c in percl)
                / max(n_dec, 1)) * 1e6

    sim_b = Simulation(_workloads(n), seed=seed)
    shells = _controllers(n, spaces, carat_models(), cfg)
    fleet = CaratPolicy(models=gbdts, controllers=shells, backend=backend,
                        cfg=cfg)
    sim_b.attach_policy(fleet)
    sim_b.run(duration_s)
    us_fleet = fleet.mean_decision_s * 1e6

    identical = all(a.decisions == b.decisions
                    for a, b in zip(percl, shells))
    identical &= all(ca.config.dirty_cache_mb == cb.config.dirty_cache_mb
                     for ca, cb in zip(sim_a.clients, sim_b.clients))
    return us_percl, us_fleet, n_dec, identical


def _counters_identical(sim_a, sim_b) -> bool:
    """Every cumulative counter + gauge on every client, bit-for-bit."""
    for ca, cb in zip(sim_a.clients, sim_b.clients):
        for op in ("read", "write"):
            oa, ob = ca.stats.op(op), cb.stats.op(op)
            for f in OP_FIELDS:
                if getattr(oa, f) != getattr(ob, f):
                    return False
        if (ca.dirty_bytes != cb.dirty_bytes
                or ca.stats.dirty_peak_bytes != cb.stats.dirty_peak_bytes
                or ca.stats.inflight_peak != cb.stats.inflight_peak):
            return False
    return True


def soa_replay_identity(seed=3):
    """scalar vs soa over the bundled replay corpus with a CARAT policy
    attached: decisions, counters, and throughput must be bit-identical."""
    spaces = default_spaces()
    out = {}
    for name in bundled_traces():
        tr = load_bundled_trace(name)
        runs = {}
        for backend in ("scalar", "soa"):
            sim, scheds = simulation_from_trace(tr, backend=backend,
                                                seed=seed)
            fleet = sim.attach_policy(CaratPolicy(
                spaces, carat_models(), cfg=CaratConfig(), backend="numpy"))
            duration = max(s.duration for s in scheds.values())
            res = sim.run(duration)
            runs[backend] = (sim, fleet, res)
        sim_a, fleet_a, res_a = runs["scalar"]
        sim_b, fleet_b, res_b = runs["soa"]
        ok = all(a.decisions == b.decisions
                 for a, b in zip(fleet_a.controllers, fleet_b.controllers))
        ok &= _counters_identical(sim_a, sim_b)
        ok &= res_a.client_throughput == res_b.client_throughput
        out[name] = ok
    return out


def soa_step_speedup(n=4096, steps=5, warm=2, seed=0):
    """Per-interval step wall time, scalar vs SoA, same fleet + seed.
    Both sims advance identically, so the timed run doubles as a
    counter-identity check at width ``n``."""
    sims = {b: Simulation(_workloads(n), seed=seed, backend=b)
            for b in ("scalar", "soa")}
    ms = {}
    for backend, sim in sims.items():
        for _ in range(warm):
            sim.step()
        t0 = time.perf_counter()
        for _ in range(steps):
            sim.step()
        ms[backend] = (time.perf_counter() - t0) / steps * 1e3
    identical = _counters_identical(sims["scalar"], sims["soa"])
    return ms["scalar"], ms["soa"], ms["scalar"] / ms["soa"], identical


def soa_100k_smoke(n=100_000, steps=10, seed=1):
    """The fleet-scale headline: 100k clients stepping in whole-array
    operations. Returns (ms_per_step, total_app_bytes)."""
    sim = Simulation(_workloads(n), seed=seed, backend="soa")
    sim.step()                       # build layout + static plan terms
    t0 = time.perf_counter()
    for _ in range(steps):
        sim.step()
    ms = (time.perf_counter() - t0) / steps * 1e3
    core = sim.core
    total = float(core.read.app_bytes.sum() + core.write.app_bytes.sum())
    return ms, total


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="bounded sweep for CI (<= 64 clients)")
    ap.add_argument("--tuner", default="conditional_score")
    # "numpy" is the bit-exact scoring path the identity gate relies on
    # (and what "auto" resolves to on CPU hosts); pass "auto" on a TPU host
    # to time the kernel path, where the gate downgrades to a warning
    # because jnp/pallas only match to float32 tolerance.
    ap.add_argument("--backend", default="numpy")
    args = ap.parse_args(argv)

    sizes = (1, 4, 16, 64) if args.smoke else (1, 4, 16, 64, 256)
    duration = 8.0 if args.smoke else 12.0

    failures = []
    speedup_at_64 = None
    for n in sizes:
        us_percl, us_fleet, n_dec, identical = run_pair(
            n, duration, tuner=args.tuner, backend=args.backend)
        speedup = us_percl / max(us_fleet, 1e-9)
        emit(f"fleet_scale_percl_n{n}", us_percl, n_dec)
        emit(f"fleet_scale_fleet_n{n}", us_fleet,
             f"{speedup:.1f}x|identical={identical}")
        if n == 64:
            speedup_at_64 = speedup
        if not identical:
            msg = (f"n={n}: fleet decisions diverged "
                   f"from the per-client path")
            if args.backend == "numpy":
                failures.append(msg)
            else:
                print(f"WARN: {msg} (backend={args.backend} is not "
                      f"bit-exact; rerun with --backend numpy to gate)",
                      file=sys.stderr)

    if speedup_at_64 is not None and speedup_at_64 < 5.0:
        failures.append(f"per-decision speedup at 64 clients is "
                        f"{speedup_at_64:.1f}x (< 5x target)")

    report = {"sizes": list(sizes), "decision_speedup_at_64": speedup_at_64}

    # -- SoA gate 1: replay-corpus identity (hard) -------------------------
    replay_ok = soa_replay_identity()
    report["soa_replay_identical"] = replay_ok
    emit("fleet_scale_soa_replay", 0.0,
         "identical=" + ",".join(f"{k}:{v}" for k, v in replay_ok.items()))
    for name, ok in replay_ok.items():
        if not ok:
            failures.append(f"SoA backend diverged from the scalar oracle "
                            f"on replay trace {name!r}")

    # -- SoA gate 2: >= 20x per-interval step speedup at 4096 (hard) -------
    n_speed = 4096
    ms_scalar, ms_soa, step_speedup, step_identical = soa_step_speedup(
        n=n_speed, steps=(5 if args.smoke else 10))
    report["soa_step"] = {"n": n_speed, "ms_scalar": ms_scalar,
                          "ms_soa": ms_soa, "speedup": step_speedup,
                          "identical": step_identical}
    emit(f"fleet_scale_soa_step_n{n_speed}", ms_soa * 1e3,
         f"{step_speedup:.1f}x|identical={step_identical}")
    if not step_identical:
        failures.append(f"SoA counters diverged from scalar at "
                        f"n={n_speed}")
    if step_speedup < 20.0:
        failures.append(f"SoA per-interval step speedup at {n_speed} "
                        f"clients is {step_speedup:.1f}x (< 20x target)")

    # -- SoA gate 3: 100k-client smoke (hard: must complete) ---------------
    n_big = 100_000
    ms_big, bytes_big = soa_100k_smoke(n=n_big)
    report["soa_100k"] = {"n": n_big, "ms_per_step": ms_big,
                          "app_bytes": bytes_big}
    emit(f"fleet_scale_soa_step_n{n_big}", ms_big * 1e3,
         f"{bytes_big:.3e}B")
    if not bytes_big > 0:
        failures.append("100k-client SoA smoke run moved no bytes")

    report["failures"] = failures
    with open("BENCH_fleet_scale.json", "w") as f:
        json.dump(report, f, indent=2)

    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
