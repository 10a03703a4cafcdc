"""Multi-node stage-2 sweep: scalar per-node Algorithm 2 vs the batched drain.

A 32-node x 16-client fleet (bursty DLIO-style workload mix, so stage-2
inactive->active boundaries actually fire) runs with the fleet engine's
batched cache arbitration, logging every drain's demand tensor. Gates:

1. **Allocation identity** (hard): replaying every logged drain, the
   vectorized ``cache_allocation_many`` output equals the scalar
   ``cache_allocation`` run per node — and a second full simulation with
   ``stage2="scalar"`` produces the identical end-to-end trace (cache
   limits, RPC decisions, I/O bytes).
2. **Per-boundary arbiter cost** (>= 3x, relaxed under ``--smoke`` for
   noisy 2-CPU CI runners): the pre-PR engine ran one full scalar node
   retune per *client* boundary crossing (simultaneous crossings each
   paid a retune); the batched engine drains all pending nodes once per
   step. Replayed interleaved over the logged trace, medians across
   repetitions (single-run timings on shared runners swing 3-5x).
3. **Budget trading** (hard): with trading enabled, the effective node
   budgets of every drain never sum above the configured node budgets.

Emitted rows (benchmarks/common.py CSV convention) plus a
``BENCH_cache_fleet.json`` artifact with the raw numbers.

Usage:
    PYTHONPATH=src python benchmarks/bench_cache_fleet.py [--smoke]
"""
import argparse
import json
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, "benchmarks")

import numpy as np  # noqa: E402

from common import carat_models, emit  # noqa: E402

from repro.core import CaratPolicy, default_spaces  # noqa: E402
from repro.core.cache_tuner import (CacheDemand, CacheDemandBatch,  # noqa: E402
                                    cache_allocation, cache_allocation_many)
from repro.storage import Simulation, get_workload  # noqa: E402

SPACES = default_spaces()
# bursty mix: dlio_* duty cycles put whole client cohorts through the same
# >1 s inactive phase, so boundaries cross in bulk (the fleet-scale regime)
WL_CYCLE = ("dlio_bert", "dlio_bert", "dlio_megatron", "s_wr_sq_1m")


def build(n_nodes, clients_per_node, seed, stage2, budget_frac=0.35,
          trading=False, budgets=None, log=False):
    n = n_nodes * clients_per_node
    wls = [get_workload(WL_CYCLE[i % len(WL_CYCLE)]) for i in range(n)]
    topology = [i // clients_per_node for i in range(n)]
    if budgets is None:
        budgets = float(SPACES.cache_max * clients_per_node * budget_frac)
    sim = Simulation(wls, seed=seed, topology=topology)
    fleet = sim.attach_policy(CaratPolicy(
        SPACES, carat_models(), backend="numpy", node_budgets_mb=budgets,
        stage2=stage2, budget_trading=trading, log_stage2=log))
    return sim, fleet


def trace_signature(sim, fleet, res):
    return ([c.config.dirty_cache_mb for c in sim.clients],
            fleet.decisions, res.app_read_bytes, res.app_write_bytes)


# ------------------------------------------------------------------ replay
def _as_rows(dem):
    """collect_rows-equivalent extraction from a logged demand list (the
    batched path's real per-drain cost)."""
    return ([d.client_id for d in dem], [d.active for d in dem],
            [d.peak_cache_bytes for d in dem],
            [d.peak_inflight_bytes for d in dem],
            [d.write_rpc_share for d in dem])


def _replay_scalar(events, per_crossing):
    """The pre-PR engine: one collect + scalar Algorithm 2 per node retune
    — per *crossing* when ``per_crossing`` (inline semantics retuned the
    node for every member that hit a boundary), else once per node."""
    t0 = time.perf_counter()
    for demands, budgets, _, crossings in events:
        for dem, b, k in zip(demands, budgets.tolist(), crossings):
            for _ in range(k if per_crossing else 1):
                fresh = [CacheDemand(d.client_id, d.active,
                                     d.peak_cache_bytes,
                                     d.peak_inflight_bytes,
                                     d.write_rpc_share) for d in dem]
                cache_allocation(fresh, SPACES, b)
    return time.perf_counter() - t0


def _replay_batched(events):
    t0 = time.perf_counter()
    for demands, budgets, _, _ in events:
        batch = CacheDemandBatch.from_rows([_as_rows(d) for d in demands],
                                           budgets)
        cache_allocation_many(batch, SPACES).tolist()
    return time.perf_counter() - t0


def replay_identity(events):
    """Every logged drain: batched allocations == scalar per node."""
    for demands, budgets, effective, _ in events:
        expected = [cache_allocation(d, SPACES, float(b))
                    for d, b in zip(demands, effective.tolist())]
        batch = CacheDemandBatch.from_rows([_as_rows(d) for d in demands],
                                           budgets)
        got = batch.unpack(cache_allocation_many(batch, SPACES, effective))
        if got != expected:
            return False
    return True


def replay_speedups(events, reps=7):
    """Median speedups over interleaved repetitions (2-CPU runners are too
    noisy for single measurements)."""
    per_boundary, per_node = [], []
    for r in range(reps):
        order = (("s", "b") if r % 2 == 0 else ("b", "s"))
        t = {}
        for kind in order:
            if kind == "b":
                t["b"] = _replay_batched(events)
            else:
                t["s"] = _replay_scalar(events, per_crossing=True)
        per_boundary.append(t["s"] / max(t["b"], 1e-12))
        per_node.append(_replay_scalar(events, per_crossing=False)
                        / max(_replay_batched(events), 1e-12))
    return float(np.median(per_boundary)), float(np.median(per_node))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short trace + relaxed speedup gate for CI")
    ap.add_argument("--nodes", type=int, default=32)
    ap.add_argument("--clients-per-node", type=int, default=16)
    args = ap.parse_args(argv)

    n_nodes, cpn = args.nodes, args.clients_per_node
    duration = 6.0 if args.smoke else 12.0
    speedup_floor = 1.5 if args.smoke else 3.0

    failures = []
    report = {"nodes": n_nodes, "clients_per_node": cpn,
              "duration_s": duration, "smoke": bool(args.smoke)}

    # -- batched run (logged) + scalar run: full end-to-end trace identity --
    sim_b, fleet_b = build(n_nodes, cpn, seed=3, stage2="batched", log=True)
    res_b = sim_b.run(duration)
    sim_s, fleet_s = build(n_nodes, cpn, seed=3, stage2="scalar")
    res_s = sim_s.run(duration)

    events = fleet_b.stage2_events
    n_boundaries = fleet_b.boundary_count
    n_retunes = fleet_b.node_retune_count
    report["node_retunes"] = n_retunes
    report["client_boundaries"] = n_boundaries
    if n_retunes == 0 or n_boundaries == 0:
        failures.append("trace produced no stage-2 boundaries — the gates "
                        "would be vacuous")

    trace_identical = (trace_signature(sim_b, fleet_b, res_b)
                       == trace_signature(sim_s, fleet_s, res_s))
    alloc_identical = replay_identity(events)
    report["trace_identical"] = trace_identical
    report["alloc_identical"] = alloc_identical
    if not trace_identical:
        failures.append("stage2='batched' end-to-end trace diverged from "
                        "stage2='scalar'")
    if not alloc_identical:
        failures.append("batched allocations diverged from the scalar "
                        "per-node path on the logged trace")

    # -- per-boundary arbiter cost ------------------------------------------
    sp_boundary, sp_node = replay_speedups(events)
    us_scalar = (_replay_scalar(events, per_crossing=True)
                 / max(n_boundaries, 1)) * 1e6
    us_batched = _replay_batched(events) / max(n_boundaries, 1) * 1e6
    report["us_per_boundary_scalar"] = us_scalar
    report["us_per_boundary_batched"] = us_batched
    report["speedup_per_boundary"] = sp_boundary
    report["speedup_per_node_retune"] = sp_node
    emit(f"cache_fleet_scalar_n{n_nodes}x{cpn}", us_scalar, n_boundaries)
    emit(f"cache_fleet_batched_n{n_nodes}x{cpn}", us_batched,
         f"{sp_boundary:.1f}x|identical={trace_identical and alloc_identical}")
    emit(f"cache_fleet_vectorize_only_n{n_nodes}x{cpn}",
         fleet_b.mean_node_retune_s * 1e6, f"{sp_node:.1f}x")
    if sp_boundary < speedup_floor:
        failures.append(f"per-boundary arbiter speedup {sp_boundary:.1f}x "
                        f"< {speedup_floor}x floor")

    # -- budget trading: never exceeds the summed node budgets --------------
    # alternate starved / surplus nodes so lending actually happens
    budgets = {node: float(SPACES.cache_max * cpn
                           * (0.15 if node % 2 else 1.5))
               for node in range(n_nodes)}
    sim_t, fleet_t = build(n_nodes, cpn, seed=3, stage2="batched",
                           trading=True, budgets=budgets, log=True)
    sim_t.run(duration)
    worst, traded = 0.0, False
    for _, raw, effective, _ in fleet_t.stage2_events:
        # each drain covers the subset of nodes with pending boundaries;
        # `raw` holds exactly those nodes' configured budgets
        worst = max(worst, float(effective.sum()) - float(raw.sum()))
        traded |= bool(np.any(effective != raw))
    report["trading_worst_overrun_mb"] = worst
    report["trading_occurred"] = traded
    emit(f"cache_fleet_trading_n{n_nodes}x{cpn}",
         fleet_t.mean_node_retune_s * 1e6,
         f"overrun={worst:.6f}MB|traded={traded}")
    if worst > 1e-6:
        failures.append(f"budget trading exceeded the summed node budgets "
                        f"by {worst:.3f} MB")
    if not traded:
        failures.append("budget trading never moved any budget — the "
                        "conservation gate would be vacuous")

    report["failures"] = failures
    with open("BENCH_cache_fleet.json", "w") as f:
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
