"""One run of one cell: set up, warm up, measure, check, report.

The window drives the program's normal entry, ``Simulation.step`` on the
``soa-jax`` backend: the fused device step of the whole fleet, then, in
CARAT cells, ``CaratPolicy`` (observe, decide with the GBDT kernel,
actuate, stage-2). It is a closed loop: the next interval starts when
the previous one has returned, and every interval ends with the device
state's activity mask on the host, so the host clock around
``Simulation.step`` covers the device's work.
"""
from __future__ import annotations

import gc
import glob
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from chipbench import check, spec, traffic
from chipbench.trace import INTERVAL, TraceReduction, reduce_file

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class RunData:
    """What the per-layer readers (``metrics/<name>.py``) read."""
    cell: str
    n_clients: int
    intervals: int
    decision_intervals: int
    window_s: float
    compiles_in_window: int
    spans_s: Dict[str, float] = field(default_factory=dict)
    trace: Optional[TraceReduction] = None
    scorer_rows: List[tuple] = field(default_factory=list)  # (op, rows)
    models: Dict[str, dict] = field(default_factory=dict)   # T, D, n_f
    n_candidates: int = 0
    n_theta: int = 0
    peaks: Dict = field(default_factory=dict)


class _CompileCounter:
    def __init__(self):
        self.n = 0
        self.on = False

    def __call__(self, event, duration, **kw):
        if self.on and event == COMPILE_EVENT:
            self.n += 1


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     f"this benchmark has no CPU path")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


class _Switches:
    """The switch instants of a schedule, without end, as the sequence
    of boundaries ``SchedulePolicy`` walks."""

    def __init__(self, sched: traffic.Schedule):
        self._sched = sched

    def __len__(self) -> int:
        return sys.maxsize

    def __getitem__(self, i: int) -> float:
        return self._sched.switch(i + 1)


class _JobSchedule:
    """One job's phases for ``SchedulePolicy``: the member its schedule
    gives at each instant, repeating for as long as the run lasts."""

    def __init__(self, sched: traffic.Schedule, client: int, specs):
        self._sched, self._client, self._specs = sched, client, specs
        self.boundaries = _Switches(sched)

    def spec_at(self, t: float):
        return self._specs[self._sched.member_at(t, self._client)]


def build(cell: spec.Cell, n: int, seed: int):
    """The fleet of one run, before any policy: the simulation, the
    traffic's draws and, for a mix with phased jobs, their schedule
    (driven by one ``SchedulePolicy``). With ``clients_per_node`` k > 1,
    node j holds clients jk ... jk+k-1."""
    from repro.storage import Simulation
    from repro.storage.client import ClientConfig
    from repro.storage.params import PFSParams
    from repro.storage.sim import SchedulePolicy
    from repro.storage.workloads import WorkloadSpec
    cfg = cell.config
    pfs = dict(cfg["pfs"], n_osts=int(cfg["n_osts"]))
    members = cell.traffic["members"]
    specs = [WorkloadSpec(**m) for m in members]
    inputs = traffic.generate(n, int(pfs["n_osts"]), len(members), seed)
    sched = None
    first = inputs.member_idx
    if "schedule" in cell.traffic:
        sched = traffic.schedule(n, cell.traffic["schedule"],
                                 [m["name"] for m in members], seed)
        first = sched.member_at(0.0)
    k = int(cfg["clients_per_node"])
    sim = Simulation([specs[i] for i in first], params=PFSParams(**pfs),
                     configs=[ClientConfig(**cfg["client_defaults"])] * n,
                     seed=inputs.sim_seed, backend="soa-jax",
                     stripe_offsets=inputs.stripe_offsets.tolist(),
                     topology=[i // k for i in range(n)] if k > 1 else None,
                     interval_s=float(cell.traffic["interval_s"]))
    if sched is not None:
        # one schedule object per job: its clients share it
        jobs = {}
        for i, j in enumerate(sched.job_of.tolist()):
            if j not in jobs:
                jobs[j] = _JobSchedule(sched, i, specs)
        sim.attach_policy(SchedulePolicy(
            {i: jobs[j] for i, j in enumerate(sched.job_of.tolist())}))
    return sim, inputs, sched


def _policy(cell: spec.Cell, sim):
    pol = cell.traffic["policy"]
    if pol["name"] == "static":
        return None
    if pol["name"] != "carat":
        raise spec.SpecError(f"unknown policy {pol['name']!r}")
    from repro.config.types import CaratConfig
    from repro.core.ml.train import load_gbdt
    from repro.core.policies.carat import CaratPolicy
    from repro.core.policy import CaratSpaces
    models = {op: load_gbdt(os.path.join(cell.bench_dir, "models",
                                         f"gbdt_{op}.npz"))
              for op in ("read", "write")}
    spaces = CaratSpaces(rpc_window_pages=tuple(pol["rpc_window_pages"]),
                         rpcs_in_flight=tuple(pol["rpcs_in_flight"]),
                         dirty_cache_mb=tuple(pol["dirty_cache_mb"]),
                         **pol["defaults"])
    cfg = CaratConfig(prob_tau=pol["prob_tau"], alpha=pol["alpha"],
                      beta=pol["beta"], tuner=pol["tuner"],
                      probe_interval_s=pol["probe_interval_s"],
                      history_k=pol["history_k"],
                      inactive_threshold_s=pol["inactive_threshold_s"],
                      reprobe_on_change=pol["reprobe_on_change"],
                      reprobe_req_ratio=pol["reprobe_req_ratio"],
                      reprobe_cooldown_s=pol["reprobe_cooldown_s"])
    return sim.attach_policy(CaratPolicy(spaces, models, cfg))


@contextmanager
def _profiler(enabled: bool):
    """Profile the device into a temporary directory; yields a list that
    receives the trace's path."""
    if not enabled:
        yield None
        return
    import jax
    out: List[str] = []
    d = tempfile.mkdtemp(prefix="chipbench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        out.extend(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                             recursive=True))
        out.append(d)


def _annotate(sim, policy):
    """Host annotations in the profiler's trace around each layer the
    benchmark calls into (traced runs only)."""
    import jax
    ann = jax.profiler.TraceAnnotation

    def wrap(fn, name):
        def inner(*a, **kw):
            with ann(name):
                return fn(*a, **kw)
        return inner

    fleet = sim.device_fleet
    fleet.step = wrap(fleet.step, "bench.fleet_step")
    if policy is not None:
        policy.decide_many = wrap(policy.decide_many, "bench.decide")
        policy.finish_step = wrap(policy.finish_step, "bench.stage2")
        grid = policy.tuner.grid_models
        for op in list(grid):
            grid[op] = _Annotated(grid[op], "bench.score")


class _Annotated:
    def __init__(self, inner, name):
        self._inner, self._name = inner, name

    def __call__(self, *a, **kw):
        import jax
        with jax.profiler.TraceAnnotation(self._name):
            return self._inner(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


class _GcClock:
    """Seconds the host spent in Python's garbage collector while on."""

    def __init__(self):
        self.on = False
        self.seconds = 0.0
        self.collections = 0
        self._t0 = 0.0

    def __call__(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0
            self.collections += 1


def _p95(values: np.ndarray, weights: Optional[np.ndarray] = None) -> float:
    if weights is not None:
        values = np.repeat(values, weights)
    return float(np.percentile(values, 95)) if values.size else float("nan")


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        n_clients: Optional[int] = None, log=sys.stderr,
        keep: Optional[dict] = None, compile_cache: bool = True) -> dict:
    """One run; returns the result object (the last stdout line).
    ``keep``, when given, receives the samples and the reference inputs
    (for the control readings)."""
    import jax
    devs = _devices(cell.chips, require_tpu)
    peaks = spec.load_peaks(devs[0].device_kind, cell.bench_dir) \
        if require_tpu else {}
    if compile_cache:
        from repro.utils.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        return _run(cell, seed, seconds, trace, t_start, n_clients, log,
                    keep, devs, peaks, counter)
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)


def _run(cell, seed, seconds, trace, t_start, n_clients, log, keep, devs,
         peaks, counter) -> dict:
    import jax
    dev = devs[0]
    phases = {"imports": time.perf_counter() - t_start}

    cfg = cell.config
    n = int(n_clients or cfg["n_clients"])
    pfs = dict(cfg["pfs"], n_osts=int(cfg["n_osts"]))
    members = cell.traffic["members"]
    sim, inputs, sched = build(cell, n, seed)
    policy = _policy(cell, sim)
    sampler = check.Sampler(sim, policy, check.sample_times(
        seed, seconds, int(cell.traffic["samples"])), schedule=sched,
        members=[m["name"] for m in members])
    if trace:
        _annotate(sim, policy)
    phases["build"] = time.perf_counter() - t_start - phases["imports"]

    # ---- warm-up: every shape the window uses compiles here ----
    t_warm = time.perf_counter()
    for i in range(int(cell.traffic["warmup_intervals"])):
        sim.step()
        if i == 0:
            jax.block_until_ready(sim.device_fleet._state)
            phases["first_interval"] = time.perf_counter() - t_warm
    sampler.warm_up()
    jax.block_until_ready(sim.device_fleet._state)
    phases["warm_up"] = time.perf_counter() - t_warm
    # what set-up built lives as long as the run: keep the collector
    # from walking it again in every full collection of the window
    gc.collect()
    gc.freeze()
    gc_clock = _GcClock()
    gc.callbacks.append(gc_clock)

    rec = None
    if trace:
        from repro.core.runtime.telemetry import recorder as telemetry
        rec = telemetry.enable(source="chipbench", capacity=1 << 18)
    times: List[float] = []
    decisions: List[int] = []
    sampled: List[int] = []
    rows0 = _scorer_calls(policy)
    with _profiler(trace) as trace_out:
        ann = (jax.profiler.TraceAnnotation if trace
               else (lambda name: nullcontext()))
        counter.on = gc_clock.on = True
        w0 = time.perf_counter()
        setup_s = w0 - t_start
        end = w0
        while True:
            now = time.perf_counter()
            if now - w0 >= seconds:
                break
            # the sampler's copies are taken outside the interval's time
            if sampler.before(now - w0):
                sampled.append(len(times))
            d0 = policy.decision_count if policy is not None else 0
            ts = time.perf_counter()
            with ann(INTERVAL):
                sim.step()
            end = time.perf_counter()
            sampler.after()
            times.append(end - ts)
            decisions.append((policy.decision_count - d0)
                             if policy is not None else 0)
        counter.on = gc_clock.on = False
    gc.callbacks.remove(gc_clock)
    gc.unfreeze()
    window_s = end - w0
    scorer_rows = _scorer_calls(policy, since=rows0)
    # samples the window did not reach are taken after it, untimed
    while sampler.open:
        sampler.before(float("inf"))
        sim.step()
        sampler.after()
    jax.block_until_ready(sim.device_fleet._state)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")

    spans = {}
    if rec is not None:
        from repro.core.runtime.telemetry import recorder as telemetry
        for ev in rec.drain().spans:
            spans[ev.name] = spans.get(ev.name, 0.0) + ev.dur
        telemetry.disable()
    red = None
    if trace:
        paths = [p for p in trace_out if p.endswith(".xplane.pb")]
        red = reduce_file(paths[0]) if paths else None
        shutil.rmtree(trace_out[-1], ignore_errors=True)

    # ---- correctness, after the window, on the host, with the
    # program's fleet freed ----
    sampler.pull()
    is_carat = policy is not None
    sampler.sim = sampler.policy = None
    del sim, policy
    gc.collect()
    ref = check.Reference(pfs=pfs, members=members,
                          member_idx=inputs.member_idx,
                          offsets=inputs.stripe_offsets,
                          policy=cell.traffic["policy"],
                          clients_per_node=int(cfg["clients_per_node"]),
                          schedule=sched, **cell.references)
    numbers = check.fleet_numbers(ref, sampler.samples)
    if is_carat:
        ref.models = {op: ref.tuner.load_model(os.path.join(
            cell.bench_dir, "models", f"gbdt_{op}.npz"))
            for op in ("read", "write")}
        numbers.update(check.tuner_numbers(ref, sampler.samples))
    if keep is not None:
        keep.update(samples=sampler.samples, ref=ref, numbers=numbers)
    checks = check.judge(numbers, cell.limits)
    # a check that compared nothing proves nothing
    compared = bool(sampler.samples) and (
        not is_carat or numbers["_decisions_compared"] > 0)
    correct = compared and all(c["ok"] for c in checks.values())

    t = np.asarray(times)
    d = np.asarray(decisions)
    e2e = {
        "client_intervals_per_s": (n * len(t) / window_s, "client-ivals/s"),
        "interval_p95_ms": (_p95(t) * 1e3, "ms"),
        "decision_p95_ms": (_p95(t, d) * 1e3 if d.sum() else None, "ms"),
        "setup_s": (setup_s, "s"),
    }
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            v, unit = e2e[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        data = RunData(
            cell=cell.name, n_clients=n, intervals=len(t),
            decision_intervals=int((d > 0).sum()), window_s=window_s,
            compiles_in_window=counter.n, spans_s=spans, trace=red,
            scorer_rows=scorer_rows, peaks=peaks)
        if is_carat:
            pol = cell.traffic["policy"]
            data.n_candidates = (len(pol["rpc_window_pages"])
                                 * len(pol["rpcs_in_flight"]))
            data.n_theta = 2
            data.models = {op: {"n_trees": m["feat"].shape[0],
                                "depth": m["feat"].shape[1],
                                "n_features": m["n_features"]}
                           for op, m in ref.models.items()}
        for m in cell.per_layer:
            v = spec.load_reader(m["name"], cell.bench_dir)(data)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace and red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
    result = {"correct": correct, "attempted": len(t), "failed": 0,
              "metrics": metrics, "device": device}
    if trace and red is not None:
        result["breakdown"] = red.breakdown()
    slow = np.argsort(-t)[:3] if t.size else []
    info = {"intervals": len(t), "window_s": window_s,
            "interval_ms_median": float(np.median(t)) * 1e3 if t.size
            else None,
            "slowest_ms": [(int(i), round(float(t[i]) * 1e3, 1))
                           for i in slow],
            "sampled": sampled,
            "setup_phases_s": {k: round(v, 3) for k, v in phases.items()},
            "gc_in_window_s": round(gc_clock.seconds, 4),
            "gc_collections": gc_clock.collections,
            "compiles_in_window": counter.n, "samples": len(sampler.samples),
            "fleet_worst_field": numbers.get("_fleet_worst_field"),
            "probs_compared": numbers.get("_probs_compared"),
            "decisions_compared": numbers.get("_decisions_compared"),
            "stage2_nodes_compared": numbers.get("_stage2_nodes_compared"),
            "bootstraps": numbers.get("_bootstraps"),
            "resets": numbers.get("_resets"),
            "switched": numbers.get("_switched")}
    print("chipbench: " + ", ".join(f"{k}={v}" for k, v in info.items()),
          file=log)
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=log)
    log.flush()
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def _scorer_calls(policy, since: Optional[dict] = None):
    """(op, rows) of each scorer batch, or the counters to diff against."""
    if policy is None:
        return [] if since is not None else {}
    now = {op: dict(g.calls) for op, g in policy.tuner.grid_models.items()}
    if since is None:
        return now
    out = []
    for op, calls in now.items():
        old = since.get(op, {})
        for (backend, rows), k in calls.items():
            for _ in range(k - old.get((backend, rows), 0)):
                out.append((op, rows))
    return out
