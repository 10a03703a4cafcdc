"""Device-resident soa-jax fleet: fused-step stability, shard->device
mapping, replay-corpus tolerance, and the jax soft-dependency contract.

The fused device step is *tolerance*-gated against the bit-identical
``soa`` host backend (segment reductions and ``.sum(axis=1)`` channel
folds reassociate — the documented soa-jax contract), and must compile
exactly once per (state, statics) shape: re-stepping never retraces,
config/workload *value* mutations re-upload statics without retracing,
and only a channel-layout (kmax) change triggers one retrace.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.storage import (PFSParams, Simulation, WORKLOADS, get_workload,
                           load_bundled_trace, simulation_from_trace)
from repro.storage.workloads import WorkloadSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = sorted(WORKLOADS.keys())


def _fleet(n=8, n_osts=4, seed=2, backend="soa-jax", topology=None):
    wls = [get_workload(NAMES[i % len(NAMES)]) for i in range(n)]
    return Simulation(wls, params=PFSParams(n_osts=n_osts), seed=seed,
                      backend=backend, topology=topology)


def _assert_close(sa: Simulation, sb: Simulation, rtol=1e-9):
    sa.core.ensure_host()
    sb.core.ensure_host()
    for op in ("read", "write"):
        for f in ("app_bytes", "rpc_count", "rpc_bytes", "lat_sum_s",
                  "blocked_s", "active_s", "inflight_time"):
            np.testing.assert_allclose(
                getattr(getattr(sb.core, op), f),
                getattr(getattr(sa.core, op), f),
                rtol=rtol, atol=1e-12, err_msg=f"{op}.{f}")
    np.testing.assert_allclose(sb.core.dirty_bytes, sa.core.dirty_bytes,
                               rtol=rtol, atol=1e-6)
    np.testing.assert_allclose(sb.cluster.wait_s, sa.cluster.wait_s,
                               rtol=rtol, atol=1e-15)
    np.testing.assert_allclose(sb.cluster.served_bytes,
                               sa.cluster.served_bytes, rtol=rtol)


# ----------------------------------------------------------- fused stepping
def test_device_fleet_matches_host_soa_within_tolerance():
    a = _fleet(backend="soa")
    b = _fleet(backend="soa-jax")
    assert b.device_fleet is not None
    a.run(8.0)
    b.run(8.0)
    _assert_close(a, b)


def test_fused_step_compiles_once_across_run():
    sim = _fleet()
    sim.run(10.0)                       # 20 intervals
    assert sim.device_fleet.n_traces == 1
    sim.run(5.0)                        # 10 more: still the same trace
    assert sim.device_fleet.n_traces == 1


def test_value_mutations_do_not_retrace():
    """Config/workload value changes re-upload statics (same shapes) —
    the jit cache must hit, with state continuity preserved."""
    a = _fleet(backend="soa")
    b = _fleet(backend="soa-jax")
    for sim in (a, b):
        sim.run(4.0)
    traces = b.device_fleet.n_traces
    for sim in (a, b):
        sim.clients[0].set_rpc_config(64, 4)
        sim.clients[1].set_cache_limit(16)
        # same n_streams as an existing max: layout (kmax) unchanged
        sim.clients[2].set_workload(WorkloadSpec(
            "switched", op="write", access="random", req_bytes=1 << 20,
            n_streams=1))
    for sim in (a, b):
        sim.run(4.0)
    assert b.device_fleet.n_traces == traces
    _assert_close(a, b)


def test_layout_change_retraces_once():
    # all single-stream: kmax == 1 until the switch below widens it
    wls = [WorkloadSpec(f"w{i}", op="write", access="seq",
                        req_bytes=1 << 20, n_streams=1) for i in range(4)]
    sim = Simulation(wls, params=PFSParams(n_osts=4), seed=2,
                     backend="soa-jax")
    sim.run(2.0)
    before = sim.device_fleet.n_traces
    assert sim.core._layout[0].shape[1] == 1
    sim.clients[0].set_workload(WorkloadSpec(
        "wide", op="write", access="seq", req_bytes=1 << 20,
        n_streams=sim.p.n_osts))              # kmax 1 -> n_osts
    sim.run(2.0)
    assert sim.core._layout[0].shape[1] == sim.p.n_osts
    assert sim.device_fleet.n_traces == before + 1
    sim.run(2.0)                              # and only once
    assert sim.device_fleet.n_traces == before + 1


def test_host_views_read_through_device_state():
    """Mid-run per-client stat reads must see the device state (lazy
    sync), and host-path phases after device steps must not lose it."""
    a = _fleet(backend="soa")
    b = _fleet(backend="soa-jax")
    dt = a.interval_s
    for _ in range(6):
        a.step()
        b.step()
    assert b.device_fleet.host_stale
    for ca, cb in zip(a.clients, b.clients):
        np.testing.assert_allclose(cb.stats.read.app_bytes,
                                   ca.stats.read.app_bytes, rtol=1e-9)
        np.testing.assert_allclose(cb.stats.dirty_bytes,
                                   ca.stats.dirty_bytes,
                                   rtol=1e-9, atol=1e-6)
        np.testing.assert_allclose(
            [cb.last_wait[o] for o in sorted(cb.last_wait)],
            [ca.last_wait[o] for o in sorted(ca.last_wait)],
            rtol=1e-9, atol=1e-15)
    # host-path phase after device steps: ensure_host + host_mutated
    # hand state back and forth without losing either side's writes
    for sim in (a, b):
        plans = sim.plan_phase(sim.clients, sim.t, dt)
        fb = sim.resolve_phase(plans, dt)
        sim.commit_phase(sim.clients, plans, fb, dt)
        sim.t += dt
    for _ in range(4):
        a.step()
        b.step()
    _assert_close(a, b)


def test_replay_corpus_tolerance():
    """soa-jax stays tolerance-gated against soa on the bundled replay
    corpus (schedule-driven workload switches exercise the statics
    re-upload and mask-invalidation paths)."""
    for trace in ("mixed_shift", "dlio_epochs"):
        tr = load_bundled_trace(trace)
        res = {}
        for backend in ("soa", "soa-jax"):
            sim, _ = simulation_from_trace(tr, backend=backend)
            res[backend] = sim.run(12.0)
        np.testing.assert_allclose(res["soa-jax"].app_read_bytes,
                                   res["soa"].app_read_bytes, rtol=1e-9)
        np.testing.assert_allclose(res["soa-jax"].app_write_bytes,
                                   res["soa"].app_write_bytes, rtol=1e-9)


# --------------------------------------------------------- shard -> device
def test_sharded_device_fleet_matches_single_device():
    from repro.core.runtime.sharded import ShardedRuntime
    topo = [i % 4 for i in range(8)]
    a = _fleet(topology=topo)
    ra = a.run(8.0)
    b = _fleet(topology=topo)
    rt = ShardedRuntime(b, mode="sync", n_shards=3, device_map="auto")
    rb = rt.run(8.0)
    assert rt.device_fleet is not None
    np.testing.assert_allclose(rb.app_read_bytes, ra.app_read_bytes,
                               rtol=1e-9)
    np.testing.assert_allclose(rb.app_write_bytes, ra.app_write_bytes,
                               rtol=1e-9)
    np.testing.assert_allclose(np.asarray(rb.client_throughput),
                               np.asarray(ra.client_throughput),
                               rtol=1e-8, atol=1e-6)
    _assert_close(a, b)


def test_device_map_validation():
    from repro.core.runtime.sharded import ShardedRuntime
    with pytest.raises(ValueError, match="soa-jax"):
        ShardedRuntime(_fleet(backend="soa"), device_map="auto")
    with pytest.raises(ValueError, match="sync"):
        ShardedRuntime(_fleet(), mode="async", device_map="auto")
    with pytest.raises(ValueError, match="device_map"):
        ShardedRuntime(_fleet(), device_map="all")
    with pytest.raises(ValueError, match="straggler"):
        ShardedRuntime(_fleet(topology=[0, 0, 1, 1, 2, 2, 3, 3]),
                       n_shards=2, device_map="auto",
                       straggler_delay_s={0: 0.1})


@pytest.mark.slow
def test_shard_device_mapping_subprocess():
    """Forced 8 CPU devices: shards land on distinct devices, partials
    merge on the primary, and the result matches single-device soa-jax."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import numpy as np
        from repro.storage import (Simulation, PFSParams, get_workload,
                                   WORKLOADS)
        from repro.core.runtime.sharded import ShardedRuntime

        assert jax.device_count() == 8
        names = sorted(WORKLOADS.keys())
        wls = [get_workload(names[i % len(names)]) for i in range(16)]
        topo = [i % 8 for i in range(16)]
        a = Simulation(wls, params=PFSParams(n_osts=4), seed=2,
                       backend="soa-jax", topology=topo)
        ra = a.run(6.0)
        b = Simulation(wls, params=PFSParams(n_osts=4), seed=2,
                       backend="soa-jax", topology=topo)
        rt = ShardedRuntime(b, mode="sync", n_shards=8, device_map="auto")
        devs = {str(d) for d in rt.device_fleet.devices}
        assert len(devs) == 8, devs
        rb = rt.run(6.0)
        np.testing.assert_allclose(rb.app_read_bytes, ra.app_read_bytes,
                                   rtol=1e-9)
        np.testing.assert_allclose(rb.app_write_bytes, ra.app_write_bytes,
                                   rtol=1e-9)
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ------------------------------------------------------- jax soft-dependency
@pytest.mark.slow
def test_storage_layer_runs_without_jax():
    """scalar/soa must import and run with jax import-blocked; soa-jax
    must raise one actionable error naming the missing extra."""
    script = textwrap.dedent("""
        import sys

        class _BlockJax:
            def find_spec(self, name, path=None, target=None):
                if name == "jax" or name.startswith("jax."):
                    raise ImportError(f"import of {name!r} blocked for test")
                return None

        sys.meta_path.insert(0, _BlockJax())
        for mod in list(sys.modules):
            if mod == "jax" or mod.startswith("jax."):
                del sys.modules[mod]

        from repro.storage import Simulation, get_workload, WORKLOADS
        names = sorted(WORKLOADS.keys())
        wls = [get_workload(names[i % len(names)]) for i in range(4)]
        for backend in ("scalar", "soa"):
            res = Simulation(wls, seed=1, backend=backend).run(2.0)
            assert res.aggregate_throughput > 0
        try:
            Simulation(wls, seed=1, backend="soa-jax")
        except ImportError as e:
            msg = str(e)
            assert "soa-jax" in msg and "jax" in msg, msg
            assert "backend='soa'" in msg, msg
        else:
            raise AssertionError("backend='soa-jax' without jax must raise")
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
