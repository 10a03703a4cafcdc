"""Struct-of-arrays simulation core: scalar <-> SoA identity.

The scalar ``IOClient``/``PFSCluster`` path is the identity oracle: the
SoA backend must reproduce its cumulative counters, gauges, and OST
states *bit-for-bit* (the float accumulation order is part of the
contract — see ``storage/soa.py``'s module docstring). Property tests
randomize workload mixes, configs, stripe topologies, client ids, and
mid-run switches; replay and policy tests close the loop end-to-end.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import default_spaces, make_policy
from repro.core.runtime.sharded import ShardedRuntime
from repro.storage import (ClientConfig, PFSParams, Simulation, WORKLOADS,
                           get_workload, load_bundled_trace,
                           simulation_from_trace, synthesize_trace)
from repro.storage.soa import OP_FIELDS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = sorted(WORKLOADS.keys())
SPACES = default_spaces()


def _assert_identical(sa: Simulation, sb: Simulation, tag: str = "") -> None:
    """Every cumulative counter, gauge, and OST state must be equal —
    ``==``, not ``allclose``."""
    assert len(sa.clients) == len(sb.clients)
    for ca, cb in zip(sa.clients, sb.clients):
        assert ca.client_id == cb.client_id
        for op in ("read", "write"):
            oa, ob = ca.stats.op(op), cb.stats.op(op)
            for f in OP_FIELDS:
                va, vb = getattr(oa, f), getattr(ob, f)
                assert va == vb, (
                    f"{tag}: client {ca.client_id} {op}.{f}: "
                    f"{va!r} != {vb!r} (delta {va - vb!r})")
        assert ca.dirty_bytes == cb.dirty_bytes, (tag, ca.client_id)
        assert ca.stats.dirty_peak_bytes == cb.stats.dirty_peak_bytes
        assert ca.stats.inflight_peak == cb.stats.inflight_peak
        assert np.array_equal(np.asarray(ca.last_wait),
                              np.asarray(cb.last_wait)), (tag, ca.client_id)
    for oa, ob in zip(sa.cluster.osts, sb.cluster.osts):
        assert oa.wait_s == ob.wait_s
        assert oa.utilization == ob.utilization
        assert oa.inflight == ob.inflight
        assert oa.served_bytes == ob.served_bytes
        assert oa.served_rpcs == ob.served_rpcs


def _pair(workloads, *, steps, check_every=1, **kw):
    """Build scalar + soa twins, step them together, assert identity."""
    sa = Simulation(workloads, backend="scalar", **kw)
    sb = Simulation(workloads, backend="soa", **kw)
    for k in range(steps):
        sa.step()
        sb.step()
        if (k + 1) % check_every == 0:
            _assert_identical(sa, sb, f"step {k}")
    return sa, sb


# ------------------------------------------------------------- properties
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 10_000),
       n=st.integers(1, 12),
       n_osts=st.integers(1, 9),
       wl0=st.integers(0, 10_000),
       cfg0=st.integers(0, 10_000))
def test_random_fleets_bit_identical(seed, n, n_osts, wl0, cfg0):
    """Random workload mixes, configs, and stripe offsets: every counter
    on every client equals the scalar oracle at every step."""
    rng = np.random.default_rng(seed * 31 + wl0)
    wls = [get_workload(NAMES[int(rng.integers(len(NAMES)))])
           for _ in range(n)]
    crng = np.random.default_rng(cfg0)
    cfgs = [ClientConfig(
        rpc_window_pages=int(crng.integers(1, 513)),
        rpcs_in_flight=int(crng.integers(1, 33)),
        dirty_cache_mb=int(crng.integers(1, 257))) for _ in range(n)]
    offs = [int(crng.integers(0, n_osts)) for _ in range(n)]
    _pair(wls, steps=16, check_every=4, params=PFSParams(n_osts=n_osts),
          configs=cfgs, seed=seed, stripe_offsets=offs)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(2, 10))
def test_midrun_switches_bit_identical(seed, n):
    """Mid-run workload switches and tunable writes (both the setter API
    and raw ``client.config`` attribute writes) keep the backends
    identical — the SoA static-plan cache must invalidate on every
    mutation path."""
    rng = np.random.default_rng(seed)
    wls = [get_workload(NAMES[int(rng.integers(len(NAMES)))])
           for _ in range(n)]
    sa = Simulation(wls, backend="scalar", seed=seed,
                    params=PFSParams(n_osts=5))
    sb = Simulation(wls, backend="soa", seed=seed,
                    params=PFSParams(n_osts=5))
    for k in range(24):
        if k % 5 == 2:
            i = int(rng.integers(n))
            wl = get_workload(NAMES[int(rng.integers(len(NAMES)))])
            w = int(rng.integers(1, 513))
            f = int(rng.integers(1, 33))
            mb = int(rng.integers(1, 257))
            for s in (sa, sb):
                c = s.clients[i]
                c.set_workload(wl)
                if k % 2 == 0:
                    c.set_rpc_config(w, f)
                    c.set_cache_limit(mb)
                else:
                    c.config.rpc_window_pages = w
                    c.config.rpcs_in_flight = f
                    c.config.dirty_cache_mb = mb
        sa.step()
        sb.step()
        _assert_identical(sa, sb, f"switch step {k}")


def test_non_dense_ids_and_topology():
    ids = [7, 3, 100, 42, 9, 55, 2, 71]
    topo = [f"n{i // 2}" for i in range(8)]
    wls = [get_workload(NAMES[i % len(NAMES)]) for i in range(8)]
    kw = dict(params=PFSParams(n_osts=5), seed=4, client_ids=ids,
              topology=topo)
    sa, sb = _pair(wls, steps=12, **kw)
    assert [c.client_id for c in sb.clients] == ids
    assert sb.client_by_id(100) is sb.clients[2]
    assert sb.node_clients() == sa.node_clients()


def test_client_by_id_index_and_keyerror():
    wls = [get_workload(NAMES[0]) for _ in range(3)]
    for backend in ("scalar", "soa"):
        sim = Simulation(wls, backend=backend, client_ids=[5, 1, 9])
        assert sim.client_by_id(9).client_id == 9
        with pytest.raises(KeyError) as ei:
            sim.client_by_id(404)
        assert "404" in str(ei.value)


def test_backend_validation():
    with pytest.raises(ValueError):
        Simulation([get_workload(NAMES[0])], backend="cuda")


# ----------------------------------------------------------------- replay
def test_bundled_trace_replay_identical():
    tr = load_bundled_trace("mixed_shift")
    sims = {}
    res = {}
    for b in ("scalar", "soa"):
        sims[b], _ = simulation_from_trace(tr, backend=b)
        res[b] = sims[b].run(20.0)
    assert res["scalar"].client_throughput == res["soa"].client_throughput
    assert res["scalar"].app_read_bytes == res["soa"].app_read_bytes
    assert res["scalar"].app_write_bytes == res["soa"].app_write_bytes
    _assert_identical(sims["scalar"], sims["soa"], "mixed_shift")


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 500))
def test_synthetic_trace_replay_identical(seed):
    tr = synthesize_trace(seed, n_clients=3, duration_s=18.0)
    sims = {}
    for b in ("scalar", "soa"):
        sim, _ = simulation_from_trace(tr, backend=b)
        sim.run(18.0)
        sims[b] = sim
    _assert_identical(sims["scalar"], sims["soa"], f"synth {seed}")


# --------------------------------------------------------------- policies
def _synthetic_model(salt: float):
    def model(X):
        z = np.sin(X.astype(np.float64).sum(axis=1) * 12.9898 + salt)
        return (z + 1.0) / 2.0

    return model


def test_carat_policy_decision_identical():
    """The CARAT probe->tune loop reads counters through the SoA views
    and must make the same decisions it makes on scalar state."""
    models = {"read": _synthetic_model(0.0), "write": _synthetic_model(1.7)}
    out = {}
    for b in ("scalar", "soa"):
        sim = Simulation([get_workload(NAMES[i % len(NAMES)])
                          for i in range(6)], seed=5, backend=b)
        pol = sim.attach_policy(make_policy(
            "carat", spaces=SPACES, models=models, backend="numpy"))
        res = sim.run(15.0)
        out[b] = (res, pol, sim)
    ra, rb = out["scalar"][0], out["soa"][0]
    assert ra.client_throughput == rb.client_throughput
    assert [list(d) for d in out["scalar"][1].decisions] \
        == [list(d) for d in out["soa"][1].decisions]
    for ca, cb in zip(out["scalar"][2].clients, out["soa"][2].clients):
        assert ca.config.rpc_window_pages == cb.config.rpc_window_pages
        assert ca.config.rpcs_in_flight == cb.config.rpcs_in_flight
        assert ca.config.dirty_cache_mb == cb.config.dirty_cache_mb


# ---------------------------------------------------------------- sharded
def test_sharded_sync_soa_identical():
    """Sync sharded execution over SoA slices reassembles the canonical
    demand order: identical to single-process SoA *and* sharded scalar,
    and a ``soa-jax`` fleet's host phases (no ``device_map``) run the
    same NumPy core, so they are identical too."""
    wls = [get_workload(NAMES[i % len(NAMES)]) for i in range(12)]
    topo = [f"node{i // 2}" for i in range(12)]
    kw = dict(params=PFSParams(n_osts=6), seed=7, topology=topo)

    ref = Simulation(wls, backend="soa", **kw)
    ref_res = ref.run(10.0)

    sh = Simulation(wls, backend="soa", **kw)
    sh_res = ShardedRuntime(sh, mode="sync", n_shards=3).run(10.0)
    _assert_identical(ref, sh, "sharded-vs-single")
    assert ref_res.client_throughput == sh_res.client_throughput
    assert ref_res.app_read_bytes == sh_res.app_read_bytes

    sc = Simulation(wls, backend="scalar", **kw)
    sc_res = ShardedRuntime(sc, mode="sync", n_shards=3).run(10.0)
    _assert_identical(sc, sh, "sharded-scalar-vs-soa")
    assert sc_res.client_throughput == sh_res.client_throughput

    sj = Simulation(wls, backend="soa-jax", **kw)
    sj_res = ShardedRuntime(sj, mode="sync", n_shards=3).run(10.0)
    _assert_identical(ref, sj, "sharded-soa-jax-vs-single-soa")
    assert ref_res.client_throughput == sj_res.client_throughput
    assert ref_res.app_read_bytes == sj_res.app_read_bytes
    assert ref_res.app_write_bytes == sj_res.app_write_bytes


def test_sharded_async_soa_runs():
    """Async mode is not decision-identical by design; it must run the
    SoA DemandBatch echo path and move bytes."""
    wls = [get_workload(NAMES[i % len(NAMES)]) for i in range(8)]
    sim = Simulation(wls, backend="soa", seed=3,
                     topology=[f"n{i // 2}" for i in range(8)])
    res = ShardedRuntime(sim, mode="async", n_shards=2,
                         max_staleness_intervals=2).run(5.0)
    assert len(res.client_throughput) == 8
    assert sum(res.app_read_bytes) + sum(res.app_write_bytes) > 0


# ------------------------------------------------------------- view surface
def test_view_surface_matches_scalar():
    wls = [get_workload(NAMES[i % len(NAMES)]) for i in range(4)]
    sa, sb = _pair(wls, steps=6, seed=9, params=PFSParams(n_osts=4))
    for ca, cb in zip(sa.clients, sb.clients):
        assert ca.stream_osts(4) == cb.stream_osts(4)
        assert ca.stripe_offset == cb.stripe_offset
        snap_a, snap_b = ca.stats.snapshot(), cb.stats.snapshot()
        assert vars(snap_a.read) == vars(snap_b.read)
        assert vars(snap_a.write) == vars(snap_b.write)
        assert snap_a.dirty_bytes == snap_b.dirty_bytes
        assert snap_a.rpc_window_pages == snap_b.rpc_window_pages
        # snapshots are detached copies, not live views
        cb.config.rpc_window_pages = 511
        assert snap_b.rpc_window_pages != 511 or \
            ca.config.rpc_window_pages == 511


def test_config_validation_mirrors_scalar():
    sim = Simulation([get_workload(NAMES[0])], backend="soa")
    c = sim.clients[0]
    with pytest.raises(ValueError):
        c.set_rpc_config(0, 4)
    with pytest.raises(ValueError):
        c.set_cache_limit(0)


# ------------------------------------------------------ device fleet
def test_jax_backend_matches_numpy_within_tolerance():
    """``soa-jax`` steps through ``DeviceFleet``, whose fused step sums
    per OST and per client in another order than the NumPy core, so it
    is held to ``soa`` within tolerance (the documented float-
    reassociation point), not bit for bit."""
    wls = [get_workload(NAMES[i % len(NAMES)]) for i in range(6)]
    res = {}
    for b in ("soa", "soa-jax"):
        sim = Simulation(wls, params=PFSParams(n_osts=4), seed=2, backend=b)
        res[b] = sim.run(8.0)
    np.testing.assert_allclose(res["soa"].app_read_bytes,
                               res["soa-jax"].app_read_bytes, rtol=1e-9)
    np.testing.assert_allclose(res["soa"].app_write_bytes,
                               res["soa-jax"].app_write_bytes, rtol=1e-9)


@pytest.mark.slow
def test_jax_backend_multi_device_subprocess():
    """SNIPPETS-style forced host devices: ``DeviceFleet`` must hold to
    NumPy within tolerance when XLA exposes 8 CPU devices (flags must
    not leak into this process)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import numpy as np
        from repro.storage import Simulation, PFSParams, get_workload, WORKLOADS

        assert jax.device_count() == 8
        names = sorted(WORKLOADS.keys())
        wls = [get_workload(names[i % len(names)]) for i in range(8)]
        res = {}
        for b in ("soa", "soa-jax"):
            sim = Simulation(wls, params=PFSParams(n_osts=4), seed=2,
                             backend=b)
            res[b] = sim.run(6.0)
        np.testing.assert_allclose(res["soa"].app_read_bytes,
                                   res["soa-jax"].app_read_bytes, rtol=1e-9)
        np.testing.assert_allclose(res["soa"].app_write_bytes,
                                   res["soa-jax"].app_write_bytes, rtol=1e-9)
        print("OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# -------------------------------------------------------- run() accounting
def test_run_series_matches_scalar():
    """run()'s whole-array throughput series equals the scalar per-step
    Python accumulation."""
    wls = [get_workload(NAMES[i % len(NAMES)]) for i in range(5)]
    ra = Simulation(wls, backend="scalar", seed=6).run(10.0)
    rb = Simulation(wls, backend="soa", seed=6).run(10.0)
    assert ra.client_throughput == rb.client_throughput
    assert ra.app_read_bytes == rb.app_read_bytes
    assert ra.app_write_bytes == rb.app_write_bytes
