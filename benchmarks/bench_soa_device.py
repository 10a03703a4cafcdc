"""Device-resident soa-jax fleet gates: fused-step speedup, a simulated
million-client interval, and shard->device sync equivalence.

The ``soa-jax`` backend keeps per-client state in donated jax arrays
across intervals and advances the whole fleet with one fused
plan+resolve+commit jit step (``repro.storage.device.DeviceFleet``).
This bench hard-gates that the device path actually pays for itself:

1. **Fused step speedup** (hard): at 100k clients on a striped workload
   mix (multi-stream ``f_*`` + DL/HPC specs — OST striping is the normal
   parallel-file-system client shape), the device per-interval step must
   be >= 3x faster than the host-side ``soa`` step. Interleaved
   best-of-reps timing, identical fleets + seed; the timed run doubles
   as a tolerance check (rtol 1e-9) on cumulative app bytes.

2. **Million-client interval** (hard): a simulated fleet of 1,000,000
   clients steps entirely on-device in under ``MILLION_BUDGET_MS`` per
   interval (2000 ms — measured ~370 ms/interval on a single-core dev
   box, so the budget holds ~5x headroom for loaded CI runners while
   still catching per-step retraces or host round-trips, either of
   which is >10x). The run must stay on one jit trace and move bytes.

3. **Shard->device sync equivalence** (hard): ``ShardedRuntime(
   mode="sync", device_map="auto")`` over the device fleet must match
   the single-device soa-jax run within rtol 1e-9 on cumulative app
   bytes (the shard partial merge reassociates sums — the documented
   soa-jax tolerance contract).

Emitted rows (benchmarks/common.py CSV convention):
    soa_device_host_n100000,ms_per_step,backend=soa
    soa_device_step_n100000,ms_per_step,speedup|tol_ok
    soa_device_million,ms_per_interval,bytes|traces
    soa_device_sharded,0,max_rel

Raw numbers land in ``BENCH_soa_device.json``.

Usage:
    PYTHONPATH=src python benchmarks/bench_soa_device.py [--smoke]

``--smoke`` shortens the timed runs for CI; every gate still runs at
full fleet width (100k / 1M clients).
"""
import argparse
import json
import sys
import time

sys.path.insert(0, "src")
sys.path.insert(0, "benchmarks")

import jax  # noqa: E402
from common import emit  # noqa: E402

from repro.storage import Simulation, get_workload  # noqa: E402
from repro.storage.workloads import STRIPED_MIX  # noqa: E402

# single-stream mix for the million-client run (same cycle as
# bench_fleet_scale's 100k smoke, 10x wider)
WL_CYCLE = ("s_rd_rn_8k", "s_wr_sq_1m", "s_rd_sq_1m", "s_wr_rn_8k")

SPEEDUP_FLOOR = 3.0          # gate 1: device >= 3x host soa at 100k
MILLION_BUDGET_MS = 2000.0   # gate 2: stated per-interval budget
SHARDED_RTOL = 1e-9          # gate 3: sync shard merge tolerance


def _workloads(cycle, n):
    return [get_workload(cycle[i % len(cycle)]) for i in range(n)]


def _total_app_bytes(sim):
    sim.core.ensure_host()
    core = sim.core
    return (core.read.app_bytes + core.write.app_bytes)


def _sync(sim):
    if sim.device_fleet is not None:
        jax.block_until_ready(sim.device_fleet._state["dirty"])


def device_step_speedup(n=100_000, steps=6, reps=5, seed=1):
    """Interleaved best-of-``reps`` per-interval wall time of the same
    striped 100k fleet on the host ``soa`` backend vs the fused device
    step, plus an rtol-1e-9 check that the two runs agree."""
    sims = {b: Simulation(_workloads(STRIPED_MIX, n), seed=seed,
                          backend=b)
            for b in ("soa", "soa-jax")}
    for sim in sims.values():
        sim.run(2.0)         # warm: layout, statics, device push + trace
    best = {b: float("inf") for b in sims}
    for _ in range(reps):
        for b, sim in sims.items():
            t0 = time.perf_counter()
            for _ in range(steps):
                sim.step()
            _sync(sim)
            best[b] = min(best[b], (time.perf_counter() - t0) / steps * 1e3)
    a = _total_app_bytes(sims["soa"])
    b = _total_app_bytes(sims["soa-jax"])
    import numpy as np
    rel = float(np.max(np.abs(b - a) / np.maximum(np.abs(a), 1.0)))
    return best["soa"], best["soa-jax"], rel


def million_client_interval(n=1_000_000, steps=4, seed=1):
    """Steady-state per-interval wall time of a million-client fleet on
    the device path (first step pays the state upload + jit trace and is
    excluded; a per-step retrace would blow the budget and the trace
    count)."""
    sim = Simulation(_workloads(WL_CYCLE, n), seed=seed, backend="soa-jax")
    sim.step()
    _sync(sim)
    t0 = time.perf_counter()
    for _ in range(steps):
        sim.step()
    _sync(sim)
    ms = (time.perf_counter() - t0) / steps * 1e3
    total = float(_total_app_bytes(sim).sum())
    return ms, total, sim.device_fleet.n_traces


def sharded_device_match(n=512, n_shards=4, duration=8.0, seed=2):
    """Max relative divergence of the sync shard->device runtime from
    the single-device soa-jax run (cumulative app bytes, same fleet)."""
    import numpy as np
    from repro.core.runtime import ShardedRuntime
    topo = [i % n_shards for i in range(n)]
    a = Simulation(_workloads(STRIPED_MIX, n), seed=seed,
                   backend="soa-jax", topology=topo)
    a.run(duration)
    b = Simulation(_workloads(STRIPED_MIX, n), seed=seed,
                   backend="soa-jax", topology=topo)
    rt = ShardedRuntime(b, mode="sync", n_shards=n_shards,
                        device_map="auto")
    rt.run(duration)
    x = _total_app_bytes(a)
    y = _total_app_bytes(b)
    return float(np.max(np.abs(y - x) / np.maximum(np.abs(x), 1.0)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="shorter timed runs for CI (same fleet widths)")
    args = ap.parse_args(argv)

    steps = 4 if args.smoke else 6
    reps = 3 if args.smoke else 5
    failures = []
    report = {}

    # -- gate 1: fused device step >= 3x host soa at 100k (hard) -----------
    n = 100_000
    ms_host, ms_dev, rel = device_step_speedup(n=n, steps=steps, reps=reps)
    speedup = ms_host / ms_dev
    report["step_100k"] = {"n": n, "ms_host_soa": ms_host,
                           "ms_device": ms_dev, "speedup": speedup,
                           "max_rel": rel}
    emit(f"soa_device_host_n{n}", ms_host * 1e3, "backend=soa")
    emit(f"soa_device_step_n{n}", ms_dev * 1e3,
         f"{speedup:.2f}x|max_rel={rel:.2e}")
    if speedup < SPEEDUP_FLOOR:
        failures.append(f"fused device step at {n} clients is only "
                        f"{speedup:.2f}x the host soa step "
                        f"(< {SPEEDUP_FLOOR:.0f}x floor)")
    if rel > 1e-9:
        failures.append(f"device step diverged from host soa at {n} "
                        f"clients (max rel {rel:.2e} > 1e-9)")

    # -- gate 2: million-client interval under budget (hard) ---------------
    n_big = 1_000_000
    ms_big, bytes_big, traces = million_client_interval(
        n=n_big, steps=(2 if args.smoke else 4))
    report["million"] = {"n": n_big, "ms_per_interval": ms_big,
                         "budget_ms": MILLION_BUDGET_MS,
                         "app_bytes": bytes_big, "n_traces": traces}
    emit("soa_device_million", ms_big * 1e3,
         f"{bytes_big:.3e}B|traces={traces}")
    if ms_big > MILLION_BUDGET_MS:
        failures.append(f"million-client interval took {ms_big:.0f} ms "
                        f"(> {MILLION_BUDGET_MS:.0f} ms budget)")
    if traces != 1:
        failures.append(f"million-client run retraced the fused step "
                        f"({traces} traces; expected 1)")
    if not bytes_big > 0:
        failures.append("million-client run moved no bytes")

    # -- gate 3: shard->device sync equivalence (hard) ---------------------
    rel_sh = sharded_device_match(duration=(6.0 if args.smoke else 8.0))
    report["sharded"] = {"max_rel": rel_sh, "rtol": SHARDED_RTOL}
    emit("soa_device_sharded", 0.0, f"max_rel={rel_sh:.2e}")
    if rel_sh > SHARDED_RTOL:
        failures.append(f"sharded device runtime diverged from the "
                        f"single-device run (max rel {rel_sh:.2e} > "
                        f"{SHARDED_RTOL:.0e})")

    report["failures"] = failures
    with open("BENCH_soa_device.json", "w") as f:
        json.dump(report, f, indent=2)

    if failures:
        for msg in failures:
            print(f"FAIL: {msg}", file=sys.stderr)
        return 1
    return 0


def run() -> None:
    """benchmarks.run section hook: smoke-scale, raises on gate failure."""
    if main(["--smoke"]) != 0:
        raise RuntimeError("bench_soa_device gates failed (see FAIL lines)")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
