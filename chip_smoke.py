#!/usr/bin/env python3
"""Smoke of the tuned fleet's main path on one TPU chip (or four).

One process drives, through the entry points a user calls:

* models      — the production read/write GBDT pair (``get_default_models``:
                400-tree cap, depth 5), trained on the host from ``--seed``
                when the checkout has no ``.cache/``;
* fleet_1m    — a 1,000,000-client striped fleet stepped by the fused,
                donated ``DeviceFleet`` jit: one trace, bytes moved, state
                on the chip, peak device bytes; then a 16,384-client fleet
                held to the NumPy ``soa`` backend at the soa-jax contract's
                rtol 1e-9 after 8 intervals;
* tuned_fleet — ``Simulation(backend="soa-jax")`` with 16,384 clients and
                ``CaratPolicy`` at its defaults (scorer ``backend="auto"``)
                over at least three probe boundaries: every scorer batch of
                128 kernel rows or more must resolve to the compiled Pallas
                kernel, and decisions must be made and actuated;
* kernel      — ``GridGBDTScorer(backend="pallas")`` on the tuned fleet's
                16,384 client feature rows x 63 candidates against
                ``backend="numpy"`` (max |dp| <= 1e-5); the compiled program
                must hold the Mosaic kernel (``tpu_custom_call``).

``--four-chips`` runs only the sharded path and what it is compared with:
``ShardedRuntime(mode="sync", device_map="auto")`` over 4 shards of
262,144 clients, each shard's state on its own chip, against the
single-device ``DeviceFleet`` at rtol 1e-9.

There is no CPU path: without a TPU it exits 1 before any phase. Each
phase prints its seconds (compile apart from run) on its own lines; any
failed check raises and exits nonzero. On success the last line of
stdout is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``. The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``.cache/jax`` in this checkout.

    python3 chip_smoke.py [--seed N] [--four-chips]
"""
import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

N_FLEET = 1_000_000      # fused-step fleet (fleet_1m)
N_TUNED = 16_384         # tuned fleet, kernel rows, f64 agreement check
N_SHARD = 262_144        # clients per chip (--four-chips)
TOL_INTERVALS = 8        # intervals of the soa vs soa-jax agreement check
TUNED_INTERVALS = 6      # >= 3 decision-bearing probes at 0.5 s cadence
F64_RTOL = 1e-9          # soa-jax tolerance contract (bench_soa_device)
PROB_ATOL = 1e-5         # float32 kernel vs the NumPy scorer


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _on_device(tree, dev, what: str) -> None:
    import jax
    leaves = jax.tree.leaves(tree)
    bad = [x.devices() for x in leaves if x.devices() != {dev}]
    check(leaves and not bad, f"{what}: {len(bad)} of {len(leaves)} arrays "
                              f"not on {dev} (first: {bad[:1]})")


def _app_bytes(sim) -> np.ndarray:
    sim.core.ensure_host()
    return sim.core.read.app_bytes + sim.core.write.app_bytes


def _max_rel(x: np.ndarray, y: np.ndarray) -> float:
    return float(np.max(np.abs(y - x) / np.maximum(np.abs(x), 1.0)))


def _block(fleet) -> None:
    import jax
    jax.block_until_ready(fleet._state)


# ------------------------------------------------------------------ phases
def phase_models(seed: int) -> dict:
    from repro.core.ml.train import DEFAULT_CACHE, get_default_models
    cached = all(os.path.exists(os.path.join(
        DEFAULT_CACHE, f"gbdt_{op}_s{seed}.npz")) for op in ("read", "write"))
    t0 = time.perf_counter()
    m_r, m_w = get_default_models(seed=seed)
    log("models", f"{'loaded' if cached else 'trained'} in "
                  f"{time.perf_counter() - t0:.2f} s: read {m_r.n_trees} "
                  f"trees, write {m_w.n_trees} trees, depth {m_r.depth}, "
                  f"{m_r.n_features} features")
    check(m_r.depth == 5 and m_w.depth == 5, "GBDT depth is not 5")
    return {"read": m_r, "write": m_w}


def phase_fleet_1m(dev, seed: int) -> None:
    from repro.storage import Simulation
    from repro.storage.workloads import striped_fleet
    t0 = time.perf_counter()
    sim = Simulation(striped_fleet(N_FLEET), seed=seed, backend="soa-jax")
    fleet = sim.device_fleet
    log("fleet_1m", f"{N_FLEET} clients built in "
                    f"{time.perf_counter() - t0:.2f} s (host)")
    t0 = time.perf_counter()
    sim.step()
    _block(fleet)
    log("fleet_1m", f"first interval (upload + compile + run) "
                    f"{time.perf_counter() - t0:.2f} s")
    steps = 3
    t0 = time.perf_counter()
    for _ in range(steps):
        sim.step()
    _block(fleet)
    ms = (time.perf_counter() - t0) / steps * 1e3
    check(fleet.n_traces == 1, f"fused step traced {fleet.n_traces} times")
    _on_device(fleet._state, dev, "fleet state")
    _on_device(fleet._statics, dev, "fleet statics")
    moved = float(_app_bytes(sim).sum())
    check(moved > 0, "the 1M-client fleet moved no bytes")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log("fleet_1m", f"steady {ms:.3f} ms/interval over {steps} intervals, "
                    f"n_traces={fleet.n_traces}, app_bytes={moved:.6e}, "
                    f"peak_bytes_in_use={peak}")
    del sim, fleet
    gc.collect()

    # emulated f64 on the chip vs the NumPy backend, same fleet and seed
    t0 = time.perf_counter()
    a = Simulation(striped_fleet(N_TUNED), seed=seed, backend="soa")
    b = Simulation(striped_fleet(N_TUNED), seed=seed, backend="soa-jax")
    for sim in (a, b):
        for _ in range(TOL_INTERVALS):
            sim.step()
    rel = _max_rel(_app_bytes(a), _app_bytes(b))
    log("fleet_1m", f"soa-jax vs soa at {N_TUNED} clients, {TOL_INTERVALS} "
                    f"intervals: max rel app_bytes diff {rel!r} (rtol "
                    f"{F64_RTOL}) in {time.perf_counter() - t0:.2f} s")
    check(rel <= F64_RTOL, f"soa-jax diverged from soa: max rel {rel!r} > "
                           f"{F64_RTOL}")


def phase_tuned_fleet(models: dict, dev, seed: int):
    from repro.core import CaratPolicy, default_spaces
    from repro.storage import Simulation
    from repro.storage.workloads import striped_fleet
    sim = Simulation(striped_fleet(N_TUNED), seed=seed, backend="soa-jax")
    policy = sim.attach_policy(CaratPolicy(default_spaces(), models))
    version0 = sim.core._static_version
    times = []
    for _ in range(TUNED_INTERVALS):
        t0 = time.perf_counter()
        sim.step()
        _block(sim.device_fleet)
        times.append(time.perf_counter() - t0)
    probes = int(round(TUNED_INTERVALS * sim.interval_s
                       / policy.cfg.probe_interval_s))
    calls = {op: dict(g.calls) for op, g in policy.tuner.grid_models.items()}
    log("tuned_fleet", f"{N_TUNED} clients, {probes} probe boundaries, "
                       f"{policy.batch_count} decision batches, "
                       f"{policy.decision_count} decisions; scorer calls "
                       f"(backend, rows) -> count: {calls}")
    log("tuned_fleet", "interval seconds (first ones compile the fused "
                       "step and each new kernel row count): "
                       + ", ".join(f"{s:.3f}" for s in times))
    log("tuned_fleet", f"last interval {times[-1] * 1e3:.3f} ms per tuned "
                       f"interval (information only)")
    check(probes >= 3, f"only {probes} probe boundaries")
    big = [(op, be, rows) for op, c in calls.items()
           for (be, rows) in c if rows >= 128]
    check(big, "no scorer batch reached 128 kernel rows")
    check(all(be == "pallas" for _, be, _ in big),
          f"scorer batches of >= 128 rows not on Pallas: {big}")
    actuated = sum(len(c.decisions) for c in policy.controllers)
    check(policy.decision_count > 0 and actuated > 0
          and sim.core._static_version > version0,
          f"no decision actuated ({policy.decision_count} decided, "
          f"{actuated} applied, statics version {version0} -> "
          f"{sim.core._static_version})")
    check(float(_app_bytes(sim).sum()) > 0, "the tuned fleet moved no bytes")
    _on_device(sim.device_fleet._state, dev, "tuned fleet state")
    log("tuned_fleet", f"{actuated} config changes applied, statics version "
                       f"{version0} -> {sim.core._static_version}")
    return policy


def _check_kernel_compiled(packed, n_rows: int) -> None:
    """The default (platform-chosen) kernel path lowers to Mosaic."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.gbdt_infer.kernel import gbdt_logits_pallas
    x = jax.ShapeDtypeStruct((n_rows, packed.f_pad), jnp.float32)
    hlo = gbdt_logits_pallas.lower(x, packed.sel, packed.thr, packed.leaf_t,
                                   packed.base).compile().as_text()
    check("tpu_custom_call" in hlo, "GBDT kernel program has no "
                                    "tpu_custom_call")


def phase_kernel(models: dict, policy) -> None:
    from repro.kernels.gbdt_infer.ops import GridGBDTScorer
    theta = policy.spaces.theta_features()
    for op, model in models.items():
        feats = [c.builder.feature_vector(op) for c in policy.controllers]
        H = np.stack([f for f in feats if f is not None])
        check(len(H) == N_TUNED, f"{op}: {len(H)} feature rows, "
                                 f"want {N_TUNED}")
        pal = GridGBDTScorer(model, theta, backend="pallas")
        ref = GridGBDTScorer(model, theta, backend="numpy")
        t0 = time.perf_counter()
        got = pal(H)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = pal(H)
        run = time.perf_counter() - t0
        want = ref(H)
        diff = float(np.max(np.abs(got - want)))
        rows = len(H) * len(theta)
        log("kernel", f"{op}: {rows} rows, first call (compile + run) "
                      f"{first:.3f} s, second call {run:.3f} s (host "
                      f"cross-product + transfer + kernel), max |dp| vs "
                      f"numpy {diff!r}")
        check(diff <= PROB_ATOL, f"{op}: Pallas vs numpy max |dp| {diff!r} "
                                 f"> {PROB_ATOL}")
        _check_kernel_compiled(pal.packed, -(-rows // 128) * 128)


def four_chips(devs, seed: int) -> None:
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from repro.core.runtime import ShardedRuntime
    from repro.storage import Simulation
    from repro.storage.workloads import striped_fleet
    k = len(devs)
    n = N_SHARD * k
    topo = [i // N_SHARD for i in range(n)]
    duration = TOL_INTERVALS * 0.5
    t0 = time.perf_counter()
    a = Simulation(striped_fleet(n), seed=seed, backend="soa-jax",
                   topology=topo)
    a.run(duration)
    _on_device(a.device_fleet._state, devs[0], "single-device state")
    x = _app_bytes(a).copy()
    log("four_chips", f"single-device DeviceFleet, {n} clients, "
                      f"{TOL_INTERVALS} intervals: "
                      f"{time.perf_counter() - t0:.2f} s")
    del a
    gc.collect()
    t0 = time.perf_counter()
    b = Simulation(striped_fleet(n), seed=seed, backend="soa-jax",
                   topology=topo)
    rt = ShardedRuntime(b, mode="sync", n_shards=k, device_map="auto")
    fleet = rt.device_fleet
    check(list(fleet.devices) == list(devs),
          f"shards on {fleet.devices}, want one per chip {devs}")
    log("four_chips", f"sharded fleet built in "
                      f"{time.perf_counter() - t0:.2f} s (host)")
    # Each chip compiles its own copy of the per-shard plan program. XLA
    # releases the GIL while it compiles, so compile the k copies side by
    # side (a plan of the first interval per shard, then discarded)
    # instead of one after another inside the first interval.
    t1 = time.perf_counter()
    fleet._take_ownership()
    fleet._push()
    fleet._refresh_statics()
    wait = fleet._ost_state["ost_wait"]

    def plan(i):
        w = jax.device_put(wait, fleet.devices[i])
        return jax.block_until_ready(fleet._plan_fn(
            fleet._states[i], fleet._statics[i], w, b.t, b.interval_s))

    with ThreadPoolExecutor(k) as ex:
        list(ex.map(plan, range(k)))
    log("four_chips", f"{k} per-chip plan programs compiled side by side "
                      f"in {time.perf_counter() - t1:.2f} s")
    t1 = time.perf_counter()
    rt.run(duration)
    log("four_chips", f"{TOL_INTERVALS} sharded intervals (per-chip commit "
                      f"compiles included) {time.perf_counter() - t1:.2f} s")
    for i, (st, sl, dev) in enumerate(zip(fleet._states, fleet._statics,
                                          fleet.devices)):
        _on_device(st, dev, f"shard {i} state")
        _on_device(sl, dev, f"shard {i} statics")
    check([len(ix) for ix in fleet.shard_idx] == [N_SHARD] * k,
          f"shard sizes {[len(ix) for ix in fleet.shard_idx]}")
    y = _app_bytes(b)
    rel = _max_rel(x, y)
    log("four_chips", f"ShardedRuntime(sync, device_map=auto), {k} shards "
                      f"x {N_SHARD} clients on {[str(d) for d in devs]}: "
                      f"{time.perf_counter() - t0:.2f} s, max rel app_bytes "
                      f"diff vs single device {rel!r} (rtol {F64_RTOL}), "
                      f"app_bytes={float(y.sum()):.6e}")
    check(float(y.sum()) > 0, "the sharded fleet moved no bytes")
    check(rel <= F64_RTOL, f"sharded fleet diverged: max rel {rel!r} > "
                           f"{F64_RTOL}")


# -------------------------------------------------------------------- main
def run_one_chip(dev, seed: int) -> None:
    t0 = time.perf_counter()
    models = phase_models(seed)
    log("models", f"phase {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    phase_fleet_1m(dev, seed)
    log("fleet_1m", f"phase {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    policy = phase_tuned_fleet(models, dev, seed)
    log("tuned_fleet", f"phase {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    phase_kernel(models, policy)
    log("kernel", f"phase {time.perf_counter() - t0:.2f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the fleets and of the GBDT training data")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-shard ShardedRuntime path")
    args = ap.parse_args(argv)
    cache = enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); there is no CPU path",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return 1
    from repro.kernels.gbdt_infer.kernel import default_interpret
    check(not default_interpret(), "Pallas kernels would run interpreted")
    log("device", f"platform={devs[0].platform} "
                  f"device_kind={devs[0].device_kind} count={len(devs)} "
                  f"compile_cache={cache}")
    if args.four_chips:
        four_chips(devs[:4], args.seed)
    else:
        run_one_chip(devs[0], args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
