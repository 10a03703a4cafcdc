"""CARAT's batched observe pass against the per-shell oracle.

When every bound client is a row of one ``SoACore``, ``CaratPolicy.step``
observes the whole fleet in one pass over the counter arrays and the
shells' rows (``ControllerStore.probe``). Each shell's own
``CaratController.observe`` is the oracle: stepped shell by shell, a twin
fleet must leave the same pending decisions, bit-identical feature rows,
the same decisions and configurations, and the same shell state, at
every step.
"""
import io
import os
import pickle
import sys

import numpy as np
import pytest

from repro.config.types import CaratConfig
from repro.core import default_spaces
from repro.core.controller import STOCK_OBSERVE, CaratController
from repro.core.policies.carat import CaratPolicy
from repro.core.runtime.telemetry import recorder
from repro.storage import PFSParams, Simulation, get_workload, idle_workload
from repro.storage.soa import SoAClientView

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench.check import controller_state  # noqa: E402

SPACES = default_spaces()
DT = 0.5


class _Model:
    """A scorer whose output hashes every feature bit, so one ulp of
    difference in a feature row moves the decisions."""

    def __init__(self, salt: float):
        self.salt = salt

    def __call__(self, X):
        z = np.sin(X.astype(np.float64).sum(axis=1) * 12.9898 + self.salt)
        return (z + 1.0) / 2.0


MODELS = {"read": _Model(0.0), "write": _Model(1.7)}


class _Phases:
    """Moves a rotating third of the clients through read, idle and write
    phases, so they flip their op mix (re-probe, then bootstrap) and fall
    silent for over a second (a stage-2 boundary when they resume)."""
    phase = "workload"

    def __init__(self, period: int):
        self.period = period
        self.k = 0
        self.cycle = (get_workload("s_rd_sq_1m"), idle_workload(),
                      get_workload("s_wr_rn_8k"), idle_workload())

    def __call__(self, clients, t, dt):
        self.k += 1
        for i, c in enumerate(clients):
            if i % 3 == self.k % 3:
                c.set_workload(self.cycle[(self.k // self.period + i)
                                          % len(self.cycle)])


FLEETS = {
    # bursty and steady members, three clients per node
    "mixed": (["dlio_bert", "s_rd_rn_8k", "dlio_megatron", "s_wr_sq_1m",
               "f_wr_rn_8k", "vpic_io", "f_rd_sq_1m", "bdcats_io",
               "s_wr_rn_8k", "dlio_bert", "f_rd_rn_8k", "s_rd_sq_16m"], 3),
    # the benchmark's striped members, four clients per node
    "striped": (["f_rd_rn_8k", "f_wr_sq_1m", "f_rd_sq_1m", "f_wr_rn_8k",
                 "dlio_bert", "vpic_io", "dlio_megatron", "s_wr_rn_8k"] * 2,
                4),
}


CFGS = {"paper": CaratConfig(),
        "history2": CaratConfig(history_k=2),
        "no_reprobe": CaratConfig(reprobe_on_change=False)}


def _sim(fleet: str, seed: int, backend: str = "soa", cfg: str = "paper"):
    names, per_node = FLEETS[fleet]
    sim = Simulation([get_workload(n) for n in names],
                     params=PFSParams(n_osts=6), seed=seed, backend=backend,
                     topology=[f"n{i // per_node}"
                               for i in range(len(names))])
    sim.attach_policy(_Phases(period=3 + seed % 3))
    pol = sim.attach_policy(CaratPolicy(SPACES, MODELS, CFGS[cfg],
                                        backend="numpy"))
    return sim, pol


def _per_shell(pol: CaratPolicy) -> CaratPolicy:
    """Observe this policy's fleet shell by shell (the oracle)."""
    pol._soa_rows = lambda targets: None
    return pol


def _record(pol: CaratPolicy, log: list) -> None:
    """Keep, per step, what ``decide_many`` was handed."""
    decide = pol.decide_many

    def decide_many(batch):
        log.append(([(c.client_id, op) for c, op, _ in batch],
                    np.stack([f for _, _, f in batch])))
        return decide(batch)

    pol.decide_many = decide_many


def _configs(sim):
    return [(c.config.rpc_window_pages, c.config.rpcs_in_flight,
             c.config.dirty_cache_mb) for c in sim.clients]


def _assert_same_shells(pa: CaratPolicy, pb: CaratPolicy, tag) -> None:
    sa, sb = controller_state(pa), controller_state(pb)
    assert sa.keys() == sb.keys()
    for k in sa:
        assert np.array_equal(sa[k], sb[k], equal_nan=sa[k].dtype.kind
                              == "f"), (tag, k, sa[k], sb[k])
    for ca, cb in zip(pa.controllers, pb.controllers):
        assert ca.stage_factors.total_rpcs == cb.stage_factors.total_rpcs
        assert ca.builder.snapshot_count == cb.builder.snapshot_count
        assert ca.builder._prev == cb.builder._prev, (tag, ca.client_id)
        assert list(ca.builder.history) == list(cb.builder.history), (
            tag, ca.client_id)


def _step_both(sa, pa, sb, pb, steps, tag):
    la, lb = [], []
    _record(pa, la)
    _record(pb, lb)
    for k in range(steps):
        sa.step()
        sb.step()
        assert len(la) == len(lb), (tag, k)
        if la:
            (ida, fa), (idb, fb) = la[-1], lb[-1]
            assert ida == idb, (tag, k)
            assert fa.dtype == fb.dtype == np.float32
            assert np.array_equal(fa.view(np.uint32), fb.view(np.uint32)), (
                tag, k)
        assert [c.decisions for c in pa.controllers] \
            == [c.decisions for c in pb.controllers], (tag, k)
        assert _configs(sa) == _configs(sb), (tag, k)
        _assert_same_shells(pa, pb, (tag, k))
    return la


@pytest.mark.parametrize("cfg", sorted(CFGS))
@pytest.mark.parametrize("fleet", sorted(FLEETS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_observe_matches_per_shell(fleet, seed, cfg):
    sa, pa = _sim(fleet, seed, cfg=cfg)
    sb, pb = _sim(fleet, seed, cfg=cfg)
    _per_shell(pb)
    with recorder.enabled() as rec:
        log = _step_both(sa, pa, sb, pb, 44, (fleet, seed, cfg))
        counters = rec.snapshot()["counters"]
    assert counters["carat.observe_batched"] == 44 * len(sa.clients)
    # the mix exercised every rare path the pass hands back to the shells
    assert sum(len(ids) for ids, _ in log) > 0
    kinds = {d[1] for c in pa.controllers for d in c.decisions}
    if pa.cfg.reprobe_on_change:
        assert {"reprobe", "bootstrap"} <= kinds, kinds
    assert pa.boundary_count > 0
    assert pa.boundary_count == pb.boundary_count
    assert pa.node_retune_count == pb.node_retune_count


@pytest.mark.parametrize("backend", ["soa", "soa-jax", "scalar"])
def test_observe_batched_counter_and_totals(backend):
    """The pass engages on both SoA backends, counting every bound client
    per step, and not on the scalar backend; the probe, re-probe and
    bootstrap totals match the per-shell path's."""
    steps = 24
    with recorder.enabled() as rec:
        sim, _ = _sim("mixed", 5, backend)
        sim.run(steps * DT)
        got = rec.snapshot()["counters"]
    if backend == "soa-jax":
        # the pass only reads: the state stays on the device
        assert not sim.device_fleet.device_stale
    with recorder.enabled() as rec:
        sim, pol = _sim("mixed", 5, backend)
        _per_shell(pol)
        sim.run(steps * DT)
        want = rec.snapshot()["counters"]
    assert "carat.observe_batched" not in want
    if backend == "scalar":
        assert "carat.observe_batched" not in got
    else:
        assert got["carat.observe_batched"] == steps * len(sim.clients)
    for name in ("carat.probe", "carat.reprobe", "carat.bootstrap"):
        assert got.get(name) == want.get(name), name
    assert want["carat.reprobe"] > 0 and want["carat.bootstrap"] > 0


def _roundtrip(obj, sim):
    """Pickle and unpickle ``obj``, keeping the simulation and its
    clients by reference (they travel beside the policy state, as in a
    runtime's shard blob)."""
    buf = io.BytesIO()
    p = pickle.Pickler(buf)
    p.persistent_id = lambda o: (
        ("sim",) if o is sim else ("client", o.client_id)
        if isinstance(o, SoAClientView) else None)
    p.dump(obj)
    buf.seek(0)
    u = pickle.Unpickler(buf)
    by_id = {c.client_id: c for c in sim.clients}
    u.persistent_load = lambda pid: sim if pid[0] == "sim" else by_id[pid[1]]
    return u.load()


def test_pickled_shells_continue_identically():
    """Shells stepped by the batched pass carry their own rows through
    ``shard_state`` / pickle / ``merge_shard_state`` and the policy goes on
    exactly as one never restored."""
    sa, pa = _sim("striped", 7)
    sb, pb = _sim("striped", 7)
    _step_both(sa, pa, sb, pb, 10, "before")
    # whole nodes (shards never split one): n0 and n2, four clients each
    ids = [c.client_id for c in pb.controllers]
    restored = _roundtrip(pb.shard_state(ids[0:4] + ids[8:12]), sb)
    for c in restored:
        # a pickled shell owns a one-row store of its own
        assert c._slot.store is not pb._store and c._slot.store.n == 1
    pb.merge_shard_state(restored)
    assert all(c._slot.store is pb._store and c._slot.row == i
               for i, c in enumerate(pb.controllers))
    _step_both(sa, pa, sb, pb, 30, "after")


def test_pickled_policy_gathers_its_shells_again():
    """A pickled policy leaves its store behind; its first step after the
    restore gathers the rows its shells carried."""
    sa, pa = _sim("mixed", 3)
    sb, pb = _sim("mixed", 3)
    _step_both(sa, pa, sb, pb, 8, "before")
    del pb.decide_many                  # the test's recorder
    pc = _roundtrip(pb, sb)
    assert pc._store is None
    sb._tune_policies[sb._tune_policies.index(pb)] = pc
    _step_both(sa, pa, sb, pc, 16, "after")
    assert all(c._slot.store is pc._store for c in pc.controllers)


def test_overridden_observe_keeps_the_per_shell_path():
    """A shell class whose observe is not ``CaratController``'s is
    observed one by one: the pass never bypasses an override."""
    seen = []

    class Counting(CaratController):
        def observe(self, client, t, dt):
            seen.append(self.client_id)
            return super().observe(client, t, dt)

    sim, pol = _sim("mixed", 1)
    sim.step()
    assert pol._soa_rows(sim.clients) is not None
    pol.controllers = [Counting(c.client_id, SPACES, MODELS)
                       for c in pol.controllers]
    assert Counting.observe is not STOCK_OBSERVE
    assert pol._soa_rows(sim.clients) is None
    sim.step()
    assert sorted(seen) == sorted(c.client_id for c in sim.clients)


def test_shell_state_reads_and_writes_its_row():
    ctrl = CaratController(0, SPACES, MODELS)
    assert ctrl.builder._prev is None and len(ctrl.builder.history) == 0
    assert ctrl._last_sig is None and ctrl._last_reprobe_t == -np.inf
    ctrl.stage_factors.peak_cache_bytes = 5.0
    ctrl.was_inactive_long = True
    st, row = ctrl._slot.store, ctrl._slot.row
    assert st.sf_peak_cache[row] == 5.0 and st.was_inactive_long[row]
    ctrl.stage_factors.clear()
    assert ctrl.stage_factors.peak_cache_bytes == 0.0
    clone = pickle.loads(pickle.dumps(ctrl))
    assert clone.was_inactive_long is True
    assert clone.builder._slot is clone._slot


@pytest.mark.parametrize("op", ["read", "write"])
def test_metrics_many_match_the_scalar_metrics(op):
    """``compute_metrics_many`` row by row against ``compute_metrics``,
    edge cases included: no RPCs, no pages, under a channel's worth of
    channel time, a cache that grew more than the writes, unset
    tunables."""
    from repro.core.metrics import compute_metrics, compute_metrics_many
    from repro.storage.soa import OP_FIELDS
    from repro.storage.stats import ClientStats, OpCounters
    rng = np.random.default_rng(11)
    n = 400
    d = {f: rng.uniform(0.0, 1e7, n) for f in OP_FIELDS}
    for f in ("rpc_count", "rpc_pages", "channel_time", "inflight_time"):
        d[f][rng.random(n) < 0.25] = 0.0
    d["channel_time"][::3] = rng.uniform(0.0, 0.4, (n + 2) // 3)
    dirty, prev_dirty = rng.uniform(0, 4e9, n), rng.uniform(0, 4e9, n)
    window = rng.integers(0, 4097, n)
    inflight = rng.integers(0, 257, n)
    cache = rng.integers(0, 4097, n)
    many = compute_metrics_many(d, dirty, prev_dirty, window, inflight,
                                cache, op, 0.5)
    for i in range(n):
        prev = ClientStats(dirty_bytes=float(prev_dirty[i]))
        cur = ClientStats(dirty_bytes=float(dirty[i]),
                          rpc_window_pages=int(window[i]),
                          rpcs_in_flight=int(inflight[i]),
                          dirty_cache_mb=int(cache[i]))
        cur.op(op).__dict__.update(
            {f: float(d[f][i]) for f in OP_FIELDS})
        assert isinstance(prev.op(op), OpCounters)
        want = compute_metrics(cur, prev, op, 0.5)
        got = many[i]
        assert [float(v) for v in got] == [
            want.rpc_page_util, want.rpc_channel_util,
            want.unit_page_latency, want.data_volume,
            want.dirty_cache_util, want.est_cache_update], i
