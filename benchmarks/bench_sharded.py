"""Sharded fleet runtime gates: sync identity + async straggler tolerance.

Two deployments run under :class:`repro.core.runtime.ShardedRuntime`:

1. **Sync decision identity** (hard): a multi-node bursty fleet (CARAT
   with node budgets + cross-node budget trading) and a replayed
   multi-phase trace both run twice — single-process ``Simulation.run``
   vs ``ShardedRuntime(mode="sync")`` — and must produce bit-identical
   RPC decisions, cache limits, per-interval throughput series, and I/O
   bytes. A Magpie deployment repeats the check for the full-gather
   (centralized) policy shape. Sync mode's barrier + canonical demand
   ordering is a compute reshape, not an approximation.

2. **Async straggler tolerance** (hard): the same fleet in
   ``mode="async"`` runs once clean and once with one shard injected as
   a ~10x-slow straggler. The healthy shards' probe cadence (median
   wall-clock per completed interval) must stay within 1.5x of the
   no-straggler run — the bounded-staleness bus drops the straggler's
   late traffic instead of waiting for it. Also asserts the bus never
   *delivered* a message staler than ``max_staleness_intervals`` and
   that the straggler really lagged (else the gate is vacuous).

Plus two cross-process gates riding the same deployments via
:class:`repro.core.runtime.transport.ProcessRuntime`:

3. **Process-mode replay identity** (hard): the replayed multi-phase
   trace corpus re-runs with every shard in a spawned worker process
   over ``MultiprocessBus`` pipes and must stay bit-identical to the
   single-process oracle — decisions, cache limits, throughput, bytes.
4. **Kill + restore** (hard): one worker process is killed mid-run and
   its shard restored from the latest policy/client snapshot. The run
   must complete decision-identical to the unfaulted single-process
   run (no lost client state) and every stage-2 round must conserve
   the cache budget (sum of effective allocations never exceeds the
   raw demand total it was trimmed from).

Emitted rows (benchmarks/common.py CSV convention) plus a
``BENCH_sharded.json`` artifact with the raw numbers.

Usage:
    PYTHONPATH=src python benchmarks/bench_sharded.py [--smoke]
"""
import argparse
import json
import statistics
import sys

sys.path.insert(0, "src")
sys.path.insert(0, "benchmarks")

from common import carat_models, emit  # noqa: E402

from repro.core import CaratPolicy, default_spaces, make_policy  # noqa: E402
from repro.core.runtime import ShardedRuntime  # noqa: E402
from repro.core.runtime.transport import (KillShard,  # noqa: E402
                                          ProcessRuntime)
from repro.storage import (Simulation, compile_trace,  # noqa: E402
                           load_bundled_trace, get_workload,
                           simulation_from_schedules)

SPACES = default_spaces()
# bursty mix: dlio_* duty cycles put whole cohorts through >1 s inactive
# phases, so stage-2 boundaries (and budget trading) actually fire
WL_CYCLE = ("dlio_bert", "dlio_bert", "dlio_megatron", "s_wr_sq_1m")


def build_fleet(n_nodes, clients_per_node, seed=3, trading=True):
    n = n_nodes * clients_per_node
    wls = [get_workload(WL_CYCLE[i % len(WL_CYCLE)]) for i in range(n)]
    topology = [i // clients_per_node for i in range(n)]
    # alternate starved / surplus nodes so trading moves budget
    budgets = {node: float(SPACES.cache_max * clients_per_node
                           * (0.15 if node % 2 else 1.5))
               for node in range(n_nodes)}
    sim = Simulation(wls, seed=seed, topology=topology)
    fleet = sim.attach_policy(CaratPolicy(
        SPACES, carat_models(), backend="numpy",
        node_budgets_mb=budgets, budget_trading=trading))
    return sim, fleet


def signature(sim, policy, res):
    return ([c.config.dirty_cache_mb for c in sim.clients],
            getattr(policy, "decisions", None),
            res.app_read_bytes, res.app_write_bytes,
            res.client_throughput)


# ------------------------------------------------------ gate 1: identity --
def sync_identity_fleet(n_nodes, clients_per_node, duration):
    sim_a, pol_a = build_fleet(n_nodes, clients_per_node)
    res_a = sim_a.run(duration)
    sim_b, pol_b = build_fleet(n_nodes, clients_per_node)
    rt = ShardedRuntime(sim_b, mode="sync")
    res_b = rt.run(duration)
    ok = signature(sim_a, pol_a, res_a) == signature(sim_b, pol_b, res_b)
    return ok, len(rt.shards), pol_b.boundary_count, pol_b.decision_count


def sync_identity_replay(duration=None):
    schedules = compile_trace(load_bundled_trace("mpiio_strided_ckpt"))
    if duration is None:
        duration = max(s.duration for s in schedules.values())

    def build():
        sim = simulation_from_schedules(schedules, seed=3)
        pol = sim.attach_policy(CaratPolicy(SPACES, carat_models(),
                                            backend="numpy"))
        return sim, pol

    sim_a, pol_a = build()
    res_a = sim_a.run(duration)
    sim_b, pol_b = build()
    # clients have no declared topology -> one node each; merge into 2
    # shards so schedules (workload phase) cross the sharded path too
    rt = ShardedRuntime(sim_b, mode="sync", n_shards=2)
    res_b = rt.run(duration)
    ok = signature(sim_a, pol_a, res_a) == signature(sim_b, pol_b, res_b)
    return ok, pol_b.decision_count


def sync_identity_magpie(duration):
    names = [WL_CYCLE[i % len(WL_CYCLE)] for i in range(8)]

    def build():
        sim = Simulation([get_workload(n) for n in names], seed=5,
                         topology=[i // 2 for i in range(8)])
        pol = sim.attach_policy(make_policy("magpie", spaces=SPACES, seed=2,
                                            dwell=2))
        return sim, pol

    sim_a, pol_a = build()
    res_a = sim_a.run(duration)
    sim_b, pol_b = build()
    res_b = ShardedRuntime(sim_b, mode="sync").run(duration)
    return signature(sim_a, pol_a, res_a) == signature(sim_b, pol_b, res_b)


# ------------------------------------- gates 3+4: cross-process runtime --
def process_sync_identity_replay(duration=None):
    """Replay corpus, spawned workers over MultiprocessBus pipes."""
    schedules = compile_trace(load_bundled_trace("mpiio_strided_ckpt"))
    if duration is None:
        duration = max(s.duration for s in schedules.values())

    def build():
        sim = simulation_from_schedules(schedules, seed=3)
        pol = sim.attach_policy(CaratPolicy(SPACES, carat_models(),
                                            backend="numpy"))
        return sim, pol

    sim_a, pol_a = build()
    res_a = sim_a.run(duration)
    sim_b, pol_b = build()
    prt = ProcessRuntime(sim_b, mode="sync", transport="pipe", n_shards=2)
    res_b = prt.run(duration)
    ok = signature(sim_a, pol_a, res_a) == signature(sim_b, pol_b, res_b)
    return ok, pol_b.decision_count


def process_kill_restore(n_nodes, clients_per_node, duration):
    """Kill one worker mid-run, restore its shard from snapshot; the run
    must finish decision-identical with conserved budget accounting."""

    n = n_nodes * clients_per_node
    budgets = {node: float(SPACES.cache_max * clients_per_node
                           * (0.15 if node % 2 else 1.5))
               for node in range(n_nodes)}

    def build():
        # build_fleet, plus stage-2 logging so conservation is checkable
        sim = Simulation([get_workload(WL_CYCLE[i % len(WL_CYCLE)])
                          for i in range(n)],
                         seed=3,
                         topology=[i // clients_per_node for i in range(n)])
        pol = sim.attach_policy(CaratPolicy(
            SPACES, carat_models(), backend="numpy",
            node_budgets_mb=budgets, budget_trading=True,
            log_stage2=True))
        return sim, pol

    sim_a, pol_a = build()
    res_a = sim_a.run(duration)
    sim_b, pol_b = build()
    n_steps = int(round(duration / 0.5))
    prt = ProcessRuntime(
        sim_b, mode="sync", transport="pipe",
        events=[KillShard(at_interval=max(2, n_steps // 2), sid=1)],
        snapshot_every=2)
    res_b = prt.run(duration)
    identical = (signature(sim_a, pol_a, res_a)
                 == signature(sim_b, pol_b, res_b))
    no_lost_clients = (len(res_b.client_throughput) == len(sim_b.clients)
                       and len(pol_b.controllers) == len(sim_b.clients))
    conserved = bool(pol_b.stage2_events) and all(
        effective.sum() <= raw.sum() * (1 + 1e-12) + 1e-6
        for _, raw, effective, _ in pol_b.stage2_events)
    return identical, no_lost_clients, conserved, len(pol_b.stage2_events)


# ---------------------------------------------- gate 2: async stragglers --
def healthy_cadence(rt, exclude=()):
    vals = [c for sid, c in rt.probe_cadence().items() if sid not in exclude]
    return statistics.median(vals)


def async_straggler(n_nodes, clients_per_node, duration, staleness=2,
                    reps=3):
    """(cadence_ratio, report) — median over interleaved repetitions
    (wall-clock on shared 2-CPU runners is noisy)."""
    ratios, details = [], []
    for rep in range(reps):
        sim, _ = build_fleet(n_nodes, clients_per_node, seed=11 + rep,
                             trading=False)
        rt0 = ShardedRuntime(sim, mode="async",
                             max_staleness_intervals=staleness)
        rt0.run(duration)
        c0 = healthy_cadence(rt0, exclude=(0,))
        # a ~10x-slow shard: its interval costs ~10x a healthy interval
        delay = max(9.0 * c0, 0.002)
        sim, _ = build_fleet(n_nodes, clients_per_node, seed=11 + rep,
                             trading=False)
        rt1 = ShardedRuntime(sim, mode="async",
                             max_staleness_intervals=staleness,
                             straggler_delay_s={0: delay})
        rt1.run(duration)
        c1 = healthy_cadence(rt1, exclude=(0,))
        straggler_c = rt1.probe_cadence()[0]
        ratios.append(c1 / max(c0, 1e-9))
        details.append({
            "cadence_plain_ms": c0 * 1e3, "cadence_straggler_ms": c1 * 1e3,
            "straggler_cadence_ms": straggler_c * 1e3,
            "injected_delay_ms": delay * 1e3,
            "straggler_lag_x": straggler_c / max(c0, 1e-9),
            "bus": rt1.bus.stats(),
        })
    return statistics.median(ratios), details


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smaller fleet + shorter runs for CI")
    args = ap.parse_args(argv)

    n_nodes = 4 if args.smoke else 8
    cpn = 2 if args.smoke else 4
    duration = 8.0 if args.smoke else 14.0
    async_duration = 10.0 if args.smoke else 20.0

    failures = []
    report = {"smoke": bool(args.smoke), "nodes": n_nodes,
              "clients_per_node": cpn,
              # perf_trend noise classes: async cadence metrics are
              # sleep-scheduled wall clock — null skips the injected
              # delay (a constant we set, not a measurement), a number
              # widens the threshold for genuinely noisy cadences
              "_noise": {
                  "async_runs[*].injected_delay_ms": None,
                  "async_runs[*].cadence_*_ms": 1.0,
                  "async_runs[*].straggler_cadence_ms": 1.0,
              }}

    # -- 1. sync-mode decision identity --------------------------------------
    ok_fleet, n_shards, n_bounds, n_dec = sync_identity_fleet(
        n_nodes, cpn, duration)
    report["sync_identical_fleet"] = ok_fleet
    report["shards"] = n_shards
    report["stage2_boundaries"] = n_bounds
    emit(f"sharded_sync_fleet_n{n_nodes}x{cpn}", 0.0,
         f"{n_dec}dec|{n_bounds}boundaries|identical={ok_fleet}")
    if not ok_fleet:
        failures.append("sync-mode ShardedRuntime diverged from the "
                        "single-process Simulation on the multi-node fleet")
    if n_bounds == 0:
        failures.append("fleet trace fired no stage-2 boundaries — the "
                        "bus's stage-2 round went unexercised")

    ok_replay, n_dec_r = sync_identity_replay(duration=None if not args.smoke
                                              else 20.0)
    report["sync_identical_replay"] = ok_replay
    emit("sharded_sync_replay", 0.0, f"{n_dec_r}dec|identical={ok_replay}")
    if not ok_replay:
        failures.append("sync-mode ShardedRuntime diverged from the "
                        "single-process Simulation on the replayed trace")

    ok_magpie = sync_identity_magpie(duration)
    report["sync_identical_magpie"] = ok_magpie
    emit("sharded_sync_magpie", 0.0, f"identical={ok_magpie}")
    if not ok_magpie:
        failures.append("sync-mode full-gather (magpie) diverged from the "
                        "single-process path")

    # -- 3. process-mode replay identity (MultiprocessBus) --------------------
    ok_proc, n_dec_p = process_sync_identity_replay(
        duration=None if not args.smoke else 20.0)
    report["process_sync_identical_replay"] = ok_proc
    emit("sharded_process_replay", 0.0, f"{n_dec_p}dec|identical={ok_proc}")
    if not ok_proc:
        failures.append("process-mode ProcessRuntime (MultiprocessBus) "
                        "diverged from the single-process Simulation on "
                        "the replayed trace")

    # -- 4. kill one worker, restore from snapshot ----------------------------
    ok_kr, no_lost, conserved, n_s2 = process_kill_restore(
        n_nodes, cpn, duration)
    report["kill_restore_identical"] = ok_kr
    report["kill_restore_no_lost_clients"] = no_lost
    report["kill_restore_budget_conserved"] = conserved
    emit("sharded_kill_restore", 0.0,
         f"identical={ok_kr}|no_lost={no_lost}|conserved={conserved}"
         f"|{n_s2}stage2")
    if not ok_kr:
        failures.append("kill+restore-from-snapshot run diverged from the "
                        "unfaulted single-process run (client or policy "
                        "state was lost in the respawn)")
    if not no_lost:
        failures.append("kill+restore dropped clients or controllers "
                        "from the merged fleet")
    if not conserved:
        failures.append("stage-2 cache-budget accounting broke under "
                        "kill+restore (effective allocations exceed raw "
                        "demand, or no stage-2 round fired)")

    # -- 2. async straggler tolerance -----------------------------------------
    ratio, details = async_straggler(n_nodes, cpn, async_duration)
    report["async_cadence_ratio"] = ratio
    report["async_runs"] = details
    worst_stale = max(d["bus"]["max_staleness_seen"] for d in details)
    lag = statistics.median(d["straggler_lag_x"] for d in details)
    emit(f"sharded_async_straggler_n{n_nodes}x{cpn}",
         details[-1]["cadence_straggler_ms"] * 1e3,
         f"{ratio:.2f}x_cadence|straggler_{lag:.1f}x_slow|"
         f"max_staleness={worst_stale}")
    if ratio > 1.5:
        failures.append(f"healthy-shard probe cadence degraded {ratio:.2f}x "
                        f"under a straggler shard (> 1.5x floor)")
    if lag < 3.0:
        failures.append(f"injected straggler only ran {lag:.1f}x slow — the "
                        f"tolerance gate would be vacuous")
    if worst_stale > 2:
        failures.append(f"bus delivered a message {worst_stale} intervals "
                        f"stale (> max_staleness_intervals=2)")

    report["failures"] = failures
    with open("BENCH_sharded.json", "w") as f:
        json.dump(report, f, indent=2)

    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    return 0


def run() -> None:
    """benchmarks.run section hook: smoke-scale, raises on gate failure."""
    if main(["--smoke"]) != 0:
        raise RuntimeError("bench_sharded gates failed (see FAIL lines)")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
