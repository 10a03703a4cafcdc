"""Quickstart: CARAT tuning a single PFS client, then a whole fleet.

Part 1 trains (or loads) the GBDT models, runs a mismatched workload
(random 8 KB reads) under the default Lustre config and under CARAT, and
prints the decisions CARAT made — the paper's core loop in ~40 lines.

Part 2 scales the same loop to a 16-client fleet with the batched fleet
engine: one vectorized inference call per probe interval scores every
client's whole candidate space at once (``repro.core.policies.carat``),
with decisions bit-identical to the per-client loop. The scoring backend is
chosen per call by ``kernels/gbdt_infer`` ("auto": factorized numpy on
CPU hosts, the Pallas kernel on TPU hosts once the batch fills a block).

Part 3 makes the deployment multi-node: a client -> node topology wires
one stage-2 cache arbiter per node, every node's pending I/O-phase
boundary in a step is drained into ONE vectorized Algorithm 2 call over
the whole ``(nodes, clients)`` demand tensor (decision-identical to the
per-node scalar arbiter — see ``benchmarks/bench_cache_fleet.py``), and
opt-in budget trading lets nodes whose clients all fit at ``cache_max``
lend their unused budget to oversubscribed neighbours.

Part 4 replays a bundled trace (``repro.storage.replay``): phase records
are parsed and segmented into per-client ``WorkloadSchedule``s, the
simulation switches workloads at phase boundaries with carried state
preserved, and the attached fleet re-adapts across the phases —
re-probing at each detected workload change (see
``benchmarks/bench_replay.py`` for the static-baseline comparison).

Part 5 swaps the tuner itself: every tuning algorithm is a
``TuningPolicy`` (``repro.core.policies``) behind one attach point,
``sim.attach_policy(make_policy(name, ...))`` — CARAT, a static config,
DIAL-style decentralized learned clients, and a Magpie-style
centralized DRL actor are compared on the same replayed trace
(``benchmarks/bench_baselines.py`` runs the full corpus head-to-head).

Part 6 shards the deployment: a ``ShardedRuntime``
(``repro.core.runtime``) partitions the clients into node-group shards,
each advancing its own plan -> resolve -> commit loop, with tuning
traffic crossing shards only over an observation/decision bus. Sync
mode is decision-identical to the single-process run (gated by
``benchmarks/bench_sharded.py``); flipping to async mode frees every
shard to run its own probe cadence — an injected 10x-slow straggler
shard no longer drags the healthy shards' cadence down.

Part 7 flips the simulator itself to the struct-of-arrays backend
(``backend="soa"``, ``repro.storage.soa``): all per-client state lives
in dense arrays and every plan -> resolve -> commit phase is a
whole-array operation, bit-identical to the scalar object loop (gated
by ``benchmarks/bench_fleet_scale.py``) but >= 20x faster per interval
at 4096 clients — which is what makes a 100k-client fleet steppable.

Part 8 moves the fleet onto the accelerator (``backend="soa-jax"``,
``repro.storage.device``): per-client state lives in donated jax arrays
across intervals and each interval is ONE fused plan+resolve+commit jit
step — no host round-trip per phase, one compile per channel layout
(config/workload *value* changes re-upload statics without retracing).
Tolerance-gated (rtol 1e-9) against the bit-identical ``soa`` backend;
``ShardedRuntime(..., device_map="auto")`` splits the client axis
across jax devices. ``benchmarks/bench_soa_device.py`` hard-gates the
fused step at >= 3x the host soa step at 100k clients and steps a
million-client fleet per interval under a stated budget.

Part 9 takes the sharded fleet across process boundaries
(``repro.core.runtime.transport``): a ``ProcessRuntime`` pickles the
assembled simulation once and spawns one worker process per shard,
coordinated over a real transport — multiprocessing pipes
(``transport="pipe"``) or length-prefixed frames on TCP
(``transport="socket"``, the cross-host transport; workers reconnect
with bounded backoff). Payloads must pass the ``transport.wire`` purity
gate — tuner RNG position crosses as serialized state, never as a live
generator — which is what keeps sync process mode decision-identical to
the single-process run. Workers snapshot every N intervals, so a
SIGKILLed shard respawns from its latest snapshot and replays back into
the fleet with nothing lost (``benchmarks/bench_transport.py`` and the
kill+restore gate in ``benchmarks/bench_sharded.py`` hard-gate all of
this).

Part 10 turns the lights on (``repro.core.runtime.telemetry``): pass
``telemetry=True`` (and a ``flight_dir``) to ``ProcessRuntime`` and
every process — coordinator and spawned workers — records spans
(plan/resolve/commit, policy observe/decide/actuate, stage-2) and bus
counters into a preallocated ring buffer, drained over the bus each
interval. Worker clock offsets are estimated NTP-style at handshake, so
the exported Chrome/Perfetto trace (``write_trace``) lines every
process up on one timeline; a killed worker leaves a flight-recorder
postmortem JSON of its last intervals. Telemetry is off by default and
recording never touches RNG or float order, so the run stays
bit-identical — ``benchmarks/bench_overhead.py`` hard-gates identity
plus the wall-clock envelope.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

from repro.config.types import CaratConfig
from repro.core import (CaratController, CaratPolicy, NodeCacheArbiter,
                        PerClientPolicy, default_spaces)
from repro.core.ml.train import get_default_models
from repro.storage import Simulation, get_workload
from repro.storage.client import ClientConfig
from repro.storage.sim import run_static


def main():
    print("== CARAT quickstart ==")
    m_read, m_write = get_default_models()     # trains + caches on first run
    models = {"read": m_read, "write": m_write}
    spaces = default_spaces()
    wl = get_workload("s_rd_rn_8k")            # random 8 KB reads

    default = run_static(wl, ClientConfig(), duration_s=30.0, seed=7)
    print(f"default (1024 pages, 8 in-flight): {default/1e6:7.1f} MB/s")

    sim = Simulation([wl], configs=[ClientConfig()], seed=7)
    ctrl = CaratController(0, spaces, models, CaratConfig(),
                           arbiter=NodeCacheArbiter(spaces))
    sim.attach_policy(PerClientPolicy({0: ctrl}))
    res = sim.run(30.0)
    tuned = res.client_mean_throughput(0)
    print(f"CARAT (online co-tuning):           {tuned/1e6:7.1f} MB/s "
          f"({tuned/default:.2f}x)")
    print("decisions (t, op, window_pages, in_flight):")
    for d in ctrl.decisions[:10]:
        print("   ", d)
    ov = ctrl.overheads()
    print(f"overheads: snapshot {ov['snapshot_ms']:.2f} ms, "
          f"inference {ov['inference_ms']:.2f} ms "
          f"(probe interval: {CaratConfig().probe_interval_s*1e3:.0f} ms)")

    # -- Part 2: the same loop, fleet-scale ---------------------------------
    print("\n== fleet engine: 16 clients, one batched tuner ==")
    names = ["s_rd_rn_8k", "s_wr_sq_1m", "s_rd_sq_1m", "s_wr_rn_8k"] * 4
    fleet_sim = Simulation([get_workload(n) for n in names], seed=7)
    # CaratPolicy builds one controller shell per client at bind (stage
    # machine, stage-2 arbiter) and drives all of them from a single batched
    # tuner; backend="auto" picks numpy/jnp/pallas per platform + batch
    fleet = fleet_sim.attach_policy(CaratPolicy(spaces, models))
    res = fleet_sim.run(20.0)
    ov = fleet.overheads()
    print(f"aggregate throughput: {res.aggregate_throughput/1e6:7.1f} MB/s")
    print(f"decisions: {fleet.decision_count} "
          f"(cost {ov['decision_ms']*1e3:.0f} us per client decision; "
          f"one {ov['batch_ms']:.2f} ms batch scores every client)")
    print("decisions are bit-identical to the per-client loop — see "
          "benchmarks/bench_fleet_scale.py")

    # -- Part 3: multi-node stage-2 — topology + budget trading -------------
    print("\n== multi-node stage-2: 4 nodes x 4 clients, budget trading ==")
    names = ["dlio_bert", "dlio_bert", "dlio_megatron", "s_wr_sq_1m"] * 4
    # client i lives on node i // 4; the topology can also be passed to
    # CaratPolicy directly instead of declaring it on the simulation
    node_sim = Simulation([get_workload(n) for n in names], seed=7,
                          topology=[i // 4 for i in range(16)])
    # starve the odd nodes, oversize the even ones: trading moves the
    # surplus at each drain (never exceeding the summed node budgets)
    spaces_max = spaces.cache_max
    fleet = node_sim.attach_policy(CaratPolicy(
        spaces, models,
        node_budgets_mb={0: 6.0 * spaces_max, 1: 1.0 * spaces_max,
                         2: 6.0 * spaces_max, 3: 1.0 * spaces_max},
        budget_trading=True))
    res = node_sim.run(20.0)
    ov = fleet.overheads()
    print(f"aggregate throughput: {res.aggregate_throughput/1e6:7.1f} MB/s")
    print(f"stage-2: {fleet.boundary_count} client boundaries drained as "
          f"{fleet.node_retune_count} node arbitrations in "
          f"{fleet.arbiter_batch_count} batched calls "
          f"({ov['stage2_node_ms']*1e3:.0f} us per node arbitration)")
    print("per-node cache limits after tuning:")
    by_id = {c.client_id: c for c in node_sim.clients}
    for node, cids in node_sim.node_clients().items():
        mbs = [by_id[c].config.dirty_cache_mb for c in cids]
        print(f"   node {node}: {mbs} MB")

    # -- Part 4: trace-driven workload replay -------------------------------
    print("\n== workload replay: a phased trace drives the simulator ==")
    from repro.storage import (compile_trace, load_bundled_trace,
                               simulation_from_schedules)
    trace = load_bundled_trace("mixed_shift")
    schedules = compile_trace(trace)       # records -> per-client phases
    sched = schedules[0]
    print(f"trace 'mixed_shift': {trace.n_records} records segmented into "
          f"{len(sched.phases)} phases "
          f"({len(sched.active_phases())} active + idle gaps)")
    replay_sim = simulation_from_schedules(schedules, seed=7)
    fleet = replay_sim.attach_policy(CaratPolicy(spaces, models))
    res = replay_sim.run(sched.duration)
    print(f"aggregate throughput: {res.aggregate_throughput/1e6:7.1f} MB/s "
          f"over {sched.duration:.0f} s of replay")
    print("decisions across the replayed phases (reprobe = detected "
          "workload change, bootstrap = tau-free re-tune from default):")
    for d in fleet.controllers[0].decisions:
        print("   ", d)
    print(f"stage-2: {fleet.boundary_count} boundaries fired by the "
          f"trace's idle gaps")
    print("fleet vs static baselines on this trace: "
          "benchmarks/bench_replay.py")

    # -- Part 5: pluggable policies — swap the tuner, keep the simulator ----
    print("\n== pluggable policies: CARAT vs static/DIAL/Magpie ==")
    from repro.core import make_policy
    results = {}
    for name in ("static", "carat", "dial", "magpie"):
        sim = simulation_from_schedules(schedules, seed=7)
        if name == "carat":
            policy = make_policy(name, spaces=spaces, models=models)
        elif name == "static":
            policy = make_policy(name)          # Lustre default, never tuned
        else:
            policy = make_policy(name, spaces=spaces)
        sim.attach_policy(policy)               # one attach point for all
        res = sim.run(sched.duration)
        results[name] = res.aggregate_throughput
    base = results["static"]
    for name, thr in results.items():
        print(f"   {name:8s} {thr/1e6:7.1f} MB/s  ({thr/base:.2f}x static)")
    print("same simulator, same trace, same seed — the policy registry "
          "(repro.core.policies.POLICIES) is the only thing that changed;")
    print("full corpus head-to-head: benchmarks/bench_baselines.py")

    # -- Part 6: sharded fleet runtime — sync identity, async stragglers ----
    print("\n== sharded runtime: 4 node-group shards on the tuning bus ==")
    from repro.core.runtime import ShardedRuntime
    names = ["dlio_bert", "dlio_bert", "dlio_megatron", "s_wr_sq_1m"] * 4
    topology = [i // 4 for i in range(16)]      # 4 nodes -> 4 shards

    def build():
        sim = Simulation([get_workload(n) for n in names], seed=7,
                         topology=topology)
        policy = sim.attach_policy(CaratPolicy(spaces, models,
                                               backend="numpy"))
        return sim, policy

    # sync mode: barrier per probe interval, decision-identical to the
    # single-process Simulation.run (bench_sharded.py gates this)
    sim_sp, pol_sp = build()
    res_sp = sim_sp.run(12.0)
    sim_sh, pol_sh = build()
    runtime = ShardedRuntime(sim_sh, mode="sync")
    res_sh = runtime.run(12.0)
    identical = (pol_sp.decisions == pol_sh.decisions
                 and res_sp.app_read_bytes == res_sh.app_read_bytes)
    print(f"sync mode over {len(runtime.shards)} shards: decision-identical "
          f"to single-process = {identical}")

    # async mode: each shard free-runs its own probe cadence; a 10x-slow
    # straggler shard is ignored (bounded-staleness gather), not waited for
    def cadence(straggler):
        sim, _ = build()
        rt = ShardedRuntime(sim, mode="async", max_staleness_intervals=2,
                            straggler_delay_s=straggler)
        rt.run(12.0)
        healthy = [c for sid, c in rt.probe_cadence().items()
                   if sid not in (straggler or {})]
        return sum(healthy) / len(healthy), rt
    plain, _ = cadence(None)
    slowed, rt = cadence({0: 0.005})
    print(f"async probe cadence (healthy shards): "
          f"{plain*1e3:.2f} ms/interval -> {slowed*1e3:.2f} ms/interval "
          f"with a straggler shard injected "
          f"({slowed/max(plain, 1e-9):.2f}x; sync would serialize the "
          f"straggler's delay into every interval)")
    print(f"bus: {rt.bus.stats()} (stale straggler traffic is dropped, "
          f"never waited for)")

    # -- Part 7: struct-of-arrays backend — 100k-client fleets --------------
    print("\n== SoA simulation core: scalar-identical, fleet-scale ==")
    import time

    import numpy as np

    # the backend switch is one constructor argument; everything else —
    # policies, replay, sharding — is unchanged (clients become thin
    # array views with the IOClient surface)
    wl_names = ["s_rd_rn_8k", "s_wr_sq_1m", "s_rd_sq_1m", "s_wr_rn_8k"]

    def fleet(backend, n):
        return Simulation([get_workload(wl_names[i % 4]) for i in range(n)],
                          seed=11, backend=backend)

    res_scalar = fleet("scalar", 64).run(10.0)
    res_soa = fleet("soa", 64).run(10.0)
    print(f"scalar vs soa at 64 clients: bit-identical = "
          f"{res_scalar.client_throughput == res_soa.client_throughput}")

    def ms_per_step(sim, steps=5):
        sim.step()                      # build layout + static plan terms
        t0 = time.perf_counter()
        for _ in range(steps):
            sim.step()
        return (time.perf_counter() - t0) / steps * 1e3

    ms_sc = ms_per_step(fleet("scalar", 4096))
    ms_so = ms_per_step(fleet("soa", 4096))
    print(f"per-interval step at 4096 clients: {ms_sc:.1f} ms scalar -> "
          f"{ms_so:.2f} ms soa ({ms_sc / ms_so:.0f}x)")

    big = fleet("soa", 100_000)
    ms_big = ms_per_step(big)
    moved = float(big.core.read.app_bytes.sum()
                  + big.core.write.app_bytes.sum())
    print(f"100k-client fleet: {ms_big:.0f} ms/interval, "
          f"{moved / 1e12:.1f} TB of application I/O modeled in "
          f"{6 * big.interval_s:.0f} simulated seconds")
    # -- Part 8: device-resident fleet — one fused jit step per interval ----
    print("\n== Device-resident soa-jax fleet: fused jit stepping ==")
    try:
        import jax  # noqa: F401
        has_jax = True
    except ImportError:
        print("jax not installed — backend='soa-jax' raises an actionable "
              "ImportError; scalar/soa run everywhere. Skipping Part 8.")
        has_jax = False

    if has_jax:
        # same constructor switch; per-client state now lives on-device in
        # donated jax arrays, and sim.step() runs plan+resolve+commit as one
        # fused jit call (only the per-OST congestion draw stays host-side)
        dev = fleet("soa-jax", 20_000)
        dev.run(8.0)                    # 16 intervals
        host = fleet("soa", 20_000)
        host.run(8.0)
        a = host.core.read.app_bytes + host.core.write.app_bytes
        dev.core.ensure_host()          # lazy read-through of device state
        b = dev.core.read.app_bytes + dev.core.write.app_bytes
        rel = float(np.max(np.abs(b - a) / np.maximum(np.abs(a), 1.0)))
        print(f"soa vs soa-jax at 20k clients over 16 intervals: "
              f"max rel {rel:.1e} (tolerance contract: 1e-9 — XLA "
              f"reassociates the channel/OST sums), "
              f"jit traces = {dev.device_fleet.n_traces} (compile once, "
              f"re-step forever)")

        # config mutations mid-run re-upload statics without retracing; only
        # a channel-layout (stripe-width) change triggers one new trace
        dev.clients[0].set_rpc_config(64, 4)
        dev.clients[1].set_cache_limit(16)
        dev.run(2.0)
        print(f"after mid-run RPC/cache mutations: jit traces still = "
              f"{dev.device_fleet.n_traces}")

        ms_host = ms_per_step(fleet("soa", 20_000))
        ms_dev = ms_per_step(fleet("soa-jax", 20_000))
        print(f"per-interval step at 20k clients: {ms_host:.1f} ms host soa "
              f"-> {ms_dev:.1f} ms fused device step "
              f"({ms_host / max(ms_dev, 1e-9):.1f}x; the gated 100k-client "
              f"striped-fleet ratio is >= 3x — "
              f"benchmarks/bench_soa_device.py, which also steps a "
              f"1,000,000-client fleet per interval)")
        # ShardedRuntime(sim, mode="sync", device_map="auto") pins each
        # shard's slice to its own jax device and merges per-OST demand
        # partials on-device before the cluster resolve —
        # tests/test_soa_device.py runs it under
        # xla_force_host_platform_device_count=8

    # -- Part 9: cross-process fleets — spawned workers, kill + restore ----
    print("\n== cross-process fleet: spawned shard workers on the bus ==")
    from repro.core.runtime.transport import KillShard, ProcessRuntime

    names = ["dlio_bert", "dlio_bert", "dlio_megatron", "s_wr_sq_1m"] * 2
    topology = [i // 2 for i in range(8)]       # 4 nodes -> 4 shards

    def build_proc():
        sim = Simulation([get_workload(n) for n in names], seed=7,
                         topology=topology)
        policy = sim.attach_policy(CaratPolicy(spaces, models,
                                               backend="numpy"))
        return sim, policy

    # the Part 6 fleet again, but each shard is now its own spawned
    # PROCESS: the assembled sim is pickled once, every worker starts from
    # byte-identical state, and all tuning traffic crosses the process
    # boundary on the bus — obs payloads carry serialized tuner-RNG state
    # (rng.state()), never live objects (transport.wire hard-fails those)
    sim_sp, pol_sp = build_proc()
    res_sp = sim_sp.run(10.0)
    sim_pr, pol_pr = build_proc()
    prt = ProcessRuntime(sim_pr, mode="sync", transport="pipe")
    res_pr = prt.run(10.0)
    identical = (pol_sp.decisions == pol_pr.decisions
                 and res_sp.app_read_bytes == res_pr.app_read_bytes)
    print(f"pipe transport, sync mode: decision-identical to "
          f"single-process = {identical}")

    # kill a worker mid-run: every snapshot_every intervals each worker
    # publishes a retained snapshot (clients + policy state as one pickle
    # graph); the killed shard respawns from it and replays forward —
    # deterministically, with duplicates dropped — so nothing is lost
    sim_kr, pol_kr = build_proc()
    prt = ProcessRuntime(sim_kr, mode="sync", transport="pipe",
                         events=[KillShard(at_interval=8, sid=1)],
                         snapshot_every=2)
    res_kr = prt.run(10.0)
    identical = (pol_sp.decisions == pol_kr.decisions
                 and res_sp.client_throughput == res_kr.client_throughput)
    print(f"SIGKILL shard 1 at interval 8, restore from snapshot: "
          f"still identical = {identical}")

    # transport="socket" runs the same protocol over length-prefixed
    # frames on TCP — the cross-host transport. host_address=(host, port)
    # binds the coordinator; SocketBus(addr, authkey=host.authkey)
    # clients must present the hub's shared secret (an HMAC handshake
    # gates every connection before any frame is deserialized) and
    # reconnect with bounded backoff + exactly-once retry tags, so
    # workers on another terminal/host can drop and rejoin without
    # losing drained messages. bench_transport.py gates socket identity
    # on every run.
    sim_sk, pol_sk = build_proc()
    prt = ProcessRuntime(sim_sk, mode="sync", transport="socket",
                         host_address=("127.0.0.1", 0))
    prt.run(10.0)
    print(f"socket transport (loopback TCP): identical = "
          f"{pol_sp.decisions == pol_sk.decisions}, "
          f"bus stats {prt.stats()}")

    # -- Part 10: telemetry — fleet trace, metrics, flight recorder --------
    print("\n== telemetry: tracing the fleet, crashing a worker ==")
    import json
    import tempfile

    from repro.core.runtime.telemetry.flight import read_dump

    # same kill-run as above, telemetry on: every process records spans
    # and bus counters into a ring buffer and drains them to the
    # coordinator over the bus; worker clock offsets are estimated at
    # handshake so the merged trace sits on one timeline. Recording
    # reads clocks and writes its own buffers only — the run stays
    # bit-identical to the telemetry-off runs above.
    flight_dir = tempfile.mkdtemp(prefix="carat-flight-")
    sim_tl, pol_tl = build_proc()
    prt = ProcessRuntime(sim_tl, mode="sync", transport="pipe",
                         events=[KillShard(at_interval=8, sid=1)],
                         snapshot_every=2, telemetry=True,
                         flight_dir=flight_dir)
    prt.run(10.0)
    col = prt.telemetry
    print(f"telemetry on, kill+restore: still identical = "
          f"{pol_sp.decisions == pol_tl.decisions}")
    print(f"sources on the timeline: {col.sources()}, "
          f"worker clock offsets (s): "
          f"{ {s: round(o, 6) for s, o in col.clock_offsets().items()} }")

    # chrome://tracing- / Perfetto-loadable trace of the whole fleet
    trace = col.write_trace(f"{flight_dir}/trace.json")
    with open(trace) as f:
        n_events = len(json.load(f)["traceEvents"])
    print(f"wrote {trace}: {n_events} trace events "
          f"(open in Perfetto / chrome://tracing)")

    # the killed worker left a postmortem: its last intervals of spans
    # and counters, plus the final metrics snapshot
    dump = read_dump([p for p in col.flight_paths if "KillShard" in p][0])
    print(f"flight dump for {dump['source']} ({dump['reason']}): "
          f"{len(dump['spans'])} spans, last metrics "
          f"{sorted(dump['metrics']['counters'])[:3]}...")

    # coordinator-side bus counters mirror the transport's own stats
    coord = col.metrics()["coord"]["counters"]
    print(f"coord counters: published={coord.get('bus.published'):.0f} "
          f"consumed={coord.get('bus.consumed'):.0f} "
          f"(bus stats {prt.stats()['published']} published)")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
