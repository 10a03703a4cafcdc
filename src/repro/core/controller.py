"""The CARAT per-client controller — two-stage tuning (paper §III-A, Fig 5).

Stage 1 (every probe interval while I/O-active): sample counters, build the
snapshot, pick the read- or write-focused model by dominant transfer volume,
run the tuner (Algorithm 1), actuate RPC params immediately.

Stage 2 (at the I/O-inactive -> active boundary, after > 1 s of silence):
the node-scope cache arbiter collects each client's active-stage factors and
re-allocates cache limits (Algorithm 2). Cache params propagate slowly, so
they are only touched at boundaries where the previous setting's influence
has faded.

The controller is *decentralized*: it sees only its own client's counters.
Cross-client coordination exists only within a node (the paper's stats
collector, Fig 4 step 5), never across the cluster.

Within the pluggable policy layer (``repro.core.policies``) this class is
the per-client *state shell* that :class:`~repro.core.policies.CaratPolicy`
hosts: ``observe()`` is the per-client sampling/stage-machine path, and
``actuate()`` applies a stage-1 decision produced either locally
(``__call__``) or by the policy's batched ``decide_many``. A shell's
state lives at its row of a :class:`ControllerStore`; for a fleet of SoA
clients the policy observes every row at once with
:meth:`ControllerStore.probe`, the array twin of ``observe()``, which
tests hold bit-identical to it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from repro.config.types import CaratConfig
from repro.core.cache_tuner import CacheDemand, cache_allocation
from repro.core.metrics import FEATURE_NAMES, Metrics, compute_metrics_many
from repro.core.policy import CaratSpaces
from repro.core.rpc_tuner import _TunerBase, make_tuner
from repro.core.runtime.telemetry.clock import perf_s
from repro.core.runtime.telemetry.recorder import active as _telemetry
from repro.core.snapshot import Snapshot, SnapshotBuilder, feature_rows
from repro.storage.client import IOClient
from repro.storage.params import PAGE_SIZE
from repro.storage.soa import OP_FIELDS
from repro.storage.stats import ClientStats, OpCounters
from repro.utils.rng import RngStream


@dataclass
class _AppSignature:
    """Config-independent workload fingerprint from one active snapshot."""
    read_share: float                   # app read bytes / total app bytes
    req_read: Optional[float] = None    # mean app request size (bytes)
    req_write: Optional[float] = None

    @classmethod
    def of(cls, snap: Snapshot) -> "_AppSignature":
        total = snap.read_app_bytes + snap.write_app_bytes
        share = snap.read_app_bytes / total if total > 0 else 0.0
        rr = (snap.read_app_bytes / snap.read_app_requests
              if snap.read_app_requests > 0.5 else None)
        rw = (snap.write_app_bytes / snap.write_app_requests
              if snap.write_app_requests > 0.5 else None)
        return cls(read_share=share, req_read=rr, req_write=rw)

    def changed_from(self, prev: "_AppSignature", req_ratio: float) -> bool:
        # strong op-mix flip (read-dominant <-> write-dominant)
        if ((prev.read_share >= 0.7 and self.read_share <= 0.3)
                or (prev.read_share <= 0.3 and self.read_share >= 0.7)):
            return True
        for a, b in ((prev.req_read, self.req_read),
                     (prev.req_write, self.req_write)):
            if a is not None and b is not None:
                lo, hi = sorted((a, b))
                if hi > lo * req_ratio:
                    return True
        return False


# ------------------------------------------------------------ shell state
# Snapshot fields kept per history entry beside its read and write metrics
_SNAP_VALS = ("t", "read_active", "write_active", "read_app_bytes",
              "write_app_bytes", "dirty_peak_bytes", "inflight_peak",
              "window_pages", "in_flight", "dirty_cache_mb",
              "read_app_requests", "write_app_requests")
_SNAP_CASTS = {"read_active": bool, "write_active": bool,
               "window_pages": int, "in_flight": int, "dirty_cache_mb": int}
_WINDOW, _IN_FLIGHT = _SNAP_VALS.index("window_pages"), _SNAP_VALS.index(
    "in_flight")
_OP_COL = {f: k for k, f in enumerate(OP_FIELDS)}
OPS = ("read", "write")


class ControllerStore:
    """The observe state of CARAT controller shells, one row per shell.

    A shell reads and writes its state at its row (a :class:`_Slot`), as
    ``_SoAStatsView`` reads a client's counters from ``SoACore``: the
    snapshot builder's previous sample and history, the stage machine,
    the re-probe flags and the stage factors. A lone shell owns a
    one-row store. ``CaratPolicy`` gathers its fleet's shells into one
    store, so that :meth:`probe` advances every shell at once.
    """

    def __init__(self, n: int, history_k: int = 1):
        self.n = n
        self.history_k = history_k
        h = history_k + 1
        z = np.zeros
        # snapshot builder: the previous sample (cumulative read and
        # write counters, gauges, tunables) and the history, oldest first
        self.has_prev = z(n, bool)
        self.prev_ops = z((n, 2, len(OP_FIELDS)))
        self.prev_gauges = z((n, 3))      # dirty, dirty peak, in-flight peak
        self.prev_cfg = z((n, 3), np.int64)   # window, in flight, cache MB
        self.n_hist = z(n, np.int64)
        self.hist_metrics = z((n, h, 2, 6))   # read, write Table II rows
        self.hist_vals = z((n, h, len(_SNAP_VALS)))
        self.snap_time = z(n)
        self.snap_count = z(n, np.int64)
        # stage machine and phase re-probe
        self.inactive_s = z(n)
        self.was_inactive_long = z(n, bool)
        self.has_sig = z(n, bool)
        # last active signature: read share, read and write request size
        # (NaN where the signature has none)
        self.sig = np.full((n, 3), np.nan)
        self.last_reprobe_t = np.full(n, -np.inf)
        self.reprobe_pending = z(n, bool)
        self.bootstrap_pending = z(n, bool)
        # stage factors (Algorithm 2's demand)
        self.sf_saw = z(n, bool)
        self.sf_peak_cache = z(n)
        self.sf_peak_inflight = z(n)
        self.sf_write_rpcs = z(n)
        self.sf_total_rpcs = z(n)

    def _arrays(self) -> List[str]:
        return [k for k, v in vars(self).items() if isinstance(v, np.ndarray)]

    def take(self, rows: Sequence[int]) -> "ControllerStore":
        """A new store holding copies of ``rows``."""
        out = ControllerStore(0, self.history_k)
        out.n = len(rows)
        for k in self._arrays():
            setattr(out, k, getattr(self, k)[list(rows)])
        return out

    def put(self, rows: Sequence[int], src: "ControllerStore",
            src_rows: Sequence[int]) -> None:
        """Copy ``src``'s ``src_rows`` into ``rows``."""
        for k in self._arrays():
            getattr(self, k)[list(rows)] = getattr(src, k)[list(src_rows)]

    @classmethod
    def gather(cls, slots: Sequence["_Slot"]) -> "ControllerStore":
        """One store holding every slot's row, in order; each slot is
        repointed at its new row."""
        ks = {s.store.history_k for s in slots}
        if len(ks) != 1:
            raise ValueError(f"shells keep histories of {sorted(ks)} "
                             f"probes; one store holds one depth")
        out = cls(len(slots), ks.pop())
        groups: Dict[int, tuple] = {}
        for i, s in enumerate(slots):
            g = groups.setdefault(id(s.store), (s.store, [], []))
            g[1].append(i)
            g[2].append(s.row)
        for src, rows, src_rows in groups.values():
            out.put(rows, src, src_rows)
        for i, s in enumerate(slots):
            s.store, s.row = out, i
        return out

    def probe(self, ops: np.ndarray, gauges: np.ndarray, tunables: np.ndarray,
              t: float, dt: float, cfg: CaratConfig,
              default: tuple) -> "Probe":
        """One probe of every row: :meth:`CaratController.observe` as
        array operations, in the same order and with the same float64
        arithmetic, so each row ends as that shell's observe leaves it.

        ``ops`` is ``(n, 2, len(OP_FIELDS))``, every row's cumulative read
        and write counters; ``gauges`` ``(n, 3)`` its dirty bytes, dirty
        peak and in-flight peak; ``tunables`` ``(n, 3)`` its RPC window,
        RPCs in flight and dirty-cache MB. The per-client actions (stage-2
        boundary marks, re-probe resets, bootstrap picks) are the
        caller's, from the returned masks.
        """
        t0 = perf_s()
        n = self.n
        window, in_flight, cache_mb = tunables.T
        dirty, dirty_peak, inflight_peak = gauges.T
        # ---- SnapshotBuilder.sample: difference, Table II, history ----
        snap = self.has_prev.copy()
        d = ops - self.prev_ops
        met = np.stack([compute_metrics_many(
            {f: d[:, j, k] for f, k in _OP_COL.items()}, dirty,
            self.prev_gauges[:, 0], window, in_flight, cache_mb, op,
            cfg.probe_interval_s) for j, op in enumerate(OPS)], axis=1)
        rd_b = d[:, 0, _OP_COL["app_bytes"]]
        wr_b = d[:, 1, _OP_COL["app_bytes"]]
        rd_q = d[:, 0, _OP_COL["app_requests"]]
        wr_q = d[:, 1, _OP_COL["app_requests"]]
        rd_act, wr_act = rd_q > 0, wr_q > 0
        vals = np.stack([np.full(n, t), rd_act, wr_act, rd_b, wr_b,
                         dirty_peak, inflight_peak, window, in_flight,
                         cache_mb, rd_q, wr_q], axis=1)
        self.hist_metrics[snap, :-1] = self.hist_metrics[snap, 1:]
        self.hist_metrics[snap, -1] = met[snap]
        self.hist_vals[snap, :-1] = self.hist_vals[snap, 1:]
        self.hist_vals[snap, -1] = vals[snap]
        self.n_hist[snap] = np.minimum(self.n_hist[snap] + 1,
                                       self.history_k + 1)
        self.has_prev[:] = True
        self.prev_ops[:] = ops
        self.prev_gauges[:] = gauges
        self.prev_cfg[:] = tunables
        self.snap_count += 1
        self.snap_time += (perf_s() - t0) / max(n, 1)

        # ---- _StageFactors.update (every row with a snapshot) ----
        active = snap & (rd_act | wr_act)
        self.sf_saw |= active
        rd, wr = met[:, 0], met[:, 1]
        cache = wr[:, 4] * (cache_mb * 1024.0 * 1024.0)
        # max(a, b) keeps a unless b > a
        self.sf_peak_cache = np.where(snap & (cache > self.sf_peak_cache),
                                      cache, self.sf_peak_cache)
        infl = inflight_peak * window * float(PAGE_SIZE)
        self.sf_peak_inflight = np.where(
            snap & (infl > self.sf_peak_inflight), infl,
            self.sf_peak_inflight)
        self.sf_write_rpcs = np.where(snap, self.sf_write_rpcs + wr[:, 3],
                                      self.sf_write_rpcs)
        self.sf_total_rpcs = np.where(
            snap, self.sf_total_rpcs + (rd[:, 3] + wr[:, 3]),
            self.sf_total_rpcs)

        # ---- stage machine ----
        idle = snap & ~active
        self.inactive_s = np.where(idle, self.inactive_s + dt,
                                   self.inactive_s)
        self.was_inactive_long |= idle & (self.inactive_s
                                          >= cfg.inactive_threshold_s)
        boundary = active & self.was_inactive_long
        self.was_inactive_long &= ~active
        self.inactive_s[active] = 0.0

        # ---- phase re-probe (_AppSignature.of / changed_from) ----
        reset = np.zeros(n, bool)
        if cfg.reprobe_on_change:
            total = rd_b + wr_b
            with np.errstate(divide="ignore", invalid="ignore"):
                sig = np.stack([np.where(total > 0, rd_b / total, 0.0),
                                np.where(rd_q > 0.5, rd_b / rd_q, np.nan),
                                np.where(wr_q > 0.5, wr_b / wr_q, np.nan)],
                               axis=1)
            prev = self.sig
            changed = (((prev[:, 0] >= 0.7) & (sig[:, 0] <= 0.3))
                       | ((prev[:, 0] <= 0.3) & (sig[:, 0] >= 0.7)))
            for j in (1, 2):
                a, b = prev[:, j], sig[:, j]
                both = ~np.isnan(a) & ~np.isnan(b)
                changed |= both & (np.fmax(a, b)
                                   > np.fmin(a, b) * cfg.reprobe_req_ratio)
            changed &= self.has_sig
            self.sig[active] = sig[active]
            self.has_sig |= active
            self.reprobe_pending |= active & changed
            fire = active & self.reprobe_pending & (
                t - self.last_reprobe_t >= cfg.reprobe_cooldown_s)
            self.reprobe_pending &= ~fire
            self.last_reprobe_t[fire] = t
            self.bootstrap_pending |= fire
            reset = fire & ~((window == default[0])
                             & (in_flight == default[1]))

        # ---- stage 1: features; the bootstrap pick takes its row ----
        op = np.where(rd[:, 3] >= wr[:, 3], 0, 1)
        has_feats = active & ~reset & (self.n_hist >= 2)
        boot = has_feats & self.bootstrap_pending
        self.bootstrap_pending &= ~boot
        rows = np.arange(n)
        feats = feature_rows(met[rows, op],
                             self.hist_metrics[rows, -2, op],
                             window, in_flight)
        return Probe(boundary=boundary, reset=reset, bootstrap=boot,
                     pending=has_feats & ~boot, op=op, feats=feats)


class Probe(NamedTuple):
    """What :meth:`ControllerStore.probe` found, per row: the stage-2
    ``boundary`` crossings, the re-probe ``reset`` to the default, the
    ``bootstrap`` picks and the ``pending`` stage-1 decisions, with each
    row's dominant ``op`` (index into ``OPS``) and feature row."""
    boundary: np.ndarray
    reset: np.ndarray
    bootstrap: np.ndarray
    pending: np.ndarray
    op: np.ndarray
    feats: np.ndarray


class _Slot:
    """Where one shell's state lives: a row of a :class:`ControllerStore`.

    Shared by the shell, its snapshot builder and its stage-factor views,
    so repointing it moves all of them. A pickled slot carries a one-row
    store of its own."""

    __slots__ = ("store", "row")

    def __init__(self, store: ControllerStore, row: int):
        self.store = store
        self.row = row

    def __reduce__(self):
        return (_Slot, (self.store.take([self.row]), 0))


class _RowField:
    """An attribute kept at the owner's row of one store array."""

    __slots__ = ("name", "cast")

    def __init__(self, name: str, cast):
        self.name = name
        self.cast = cast

    def __get__(self, obj, owner=None):
        if obj is None:
            return self
        s = obj._slot
        return self.cast(getattr(s.store, self.name)[s.row])

    def __set__(self, obj, value) -> None:
        s = obj._slot
        getattr(s.store, self.name)[s.row] = value


class _StageFactors:
    """Factors accumulated over one I/O-active stage (for Algorithm 2),
    at a shell's row; ``_StageFactors()`` is a zeroed set of its own."""

    FIELDS = ("peak_cache_bytes", "peak_inflight_bytes", "write_rpcs",
              "total_rpcs", "saw_activity")
    __slots__ = ("_slot",)
    peak_cache_bytes = _RowField("sf_peak_cache", float)
    peak_inflight_bytes = _RowField("sf_peak_inflight", float)
    write_rpcs = _RowField("sf_write_rpcs", float)
    total_rpcs = _RowField("sf_total_rpcs", float)
    saw_activity = _RowField("sf_saw", bool)

    def __init__(self, slot: Optional[_Slot] = None):
        self._slot = slot if slot is not None else _Slot(
            ControllerStore(1), 0)

    def __repr__(self) -> str:
        return "_StageFactors(" + ", ".join(
            f"{f}={getattr(self, f)!r}" for f in self.FIELDS) + ")"

    def clear(self) -> None:
        """Start a new stage: every factor back to zero."""
        self.peak_cache_bytes = self.peak_inflight_bytes = 0.0
        self.write_rpcs = self.total_rpcs = 0.0
        self.saw_activity = False

    def update(self, snap: Snapshot) -> None:
        self.saw_activity = self.saw_activity or snap.active
        cache_bytes = snap.dirty_cache_mb * 1024.0 * 1024.0
        self.peak_cache_bytes = max(self.peak_cache_bytes,
                                    snap.write.dirty_cache_util * cache_bytes)
        vol = snap.read.data_volume + snap.write.data_volume
        inflight_bytes = snap.inflight_peak * snap.window_pages * float(PAGE_SIZE)
        self.peak_inflight_bytes = max(self.peak_inflight_bytes, inflight_bytes)
        # RPC mix for factor (3)
        self.write_rpcs += snap.write.data_volume
        self.total_rpcs += vol


class _HistoryRows:
    """``builder.history`` at a shell's row: its last snapshots, oldest
    first, standing in for the ``deque(maxlen=history_k + 1)``."""

    __slots__ = ("_slot",)

    def __init__(self, slot: _Slot):
        self._slot = slot

    @property
    def maxlen(self) -> int:
        return self._slot.store.history_k + 1

    def __len__(self) -> int:
        s = self._slot
        return int(s.store.n_hist[s.row])

    def __getitem__(self, j: int) -> Snapshot:
        n = len(self)
        if j < 0:
            j += n
        if not 0 <= j < n:
            raise IndexError("snapshot history index out of range")
        s = self._slot
        k = self.maxlen - n + j
        rd, wr = s.store.hist_metrics[s.row, k].tolist()
        vals = dict(zip(_SNAP_VALS, s.store.hist_vals[s.row, k].tolist()))
        for name, cast in _SNAP_CASTS.items():
            vals[name] = cast(vals[name])
        return Snapshot(read=Metrics(*rd), write=Metrics(*wr), **vals)

    def __iter__(self):
        return (self[j] for j in range(len(self)))

    def append(self, snap: Snapshot) -> None:
        s = self._slot
        st, r = s.store, s.row
        st.hist_metrics[r, :-1] = st.hist_metrics[r, 1:].copy()
        st.hist_vals[r, :-1] = st.hist_vals[r, 1:].copy()
        st.hist_metrics[r, -1] = [[getattr(m, f) for f in FEATURE_NAMES]
                                  for m in (snap.read, snap.write)]
        st.hist_vals[r, -1] = [getattr(snap, f) for f in _SNAP_VALS]
        st.n_hist[r] = min(int(st.n_hist[r]) + 1, self.maxlen)


class _ShellBuilder(SnapshotBuilder):
    """The shell's :class:`SnapshotBuilder`, with its previous sample,
    history and Table VIII accounting at the shell's row."""

    snapshot_time_total = _RowField("snap_time", float)
    snapshot_count = _RowField("snap_count", int)

    def __init__(self, slot: _Slot, interval_s: float, history_k: int):
        self._slot = slot
        self.interval_s = interval_s
        self.history_k = history_k

    @property
    def history(self) -> _HistoryRows:
        return _HistoryRows(self._slot)

    def feature_vector(self, op: str) -> Optional[np.ndarray]:
        # SnapshotBuilder.feature_vector, read straight from the rows
        s = self._slot
        st, r = s.store, s.row
        if st.n_hist[r] < 2:
            return None
        prev, cur = st.hist_metrics[r, -2:, OPS.index(op)]
        vals = st.hist_vals[r, -1]
        return feature_rows(cur.astype(np.float32), prev.astype(np.float32),
                            int(vals[_WINDOW]), int(vals[_IN_FLIGHT]))

    @property
    def _prev(self) -> Optional[ClientStats]:
        s = self._slot
        st, r = s.store, s.row
        if not st.has_prev[r]:
            return None
        rd, wr = st.prev_ops[r].tolist()
        dirty, peak, inflight = st.prev_gauges[r].tolist()
        window, in_flight, cache_mb = st.prev_cfg[r].tolist()
        return ClientStats(read=OpCounters(*rd), write=OpCounters(*wr),
                           dirty_bytes=dirty, dirty_peak_bytes=peak,
                           inflight_peak=inflight, rpc_window_pages=window,
                           rpcs_in_flight=in_flight, dirty_cache_mb=cache_mb)

    @_prev.setter
    def _prev(self, stats: Optional[ClientStats]) -> None:
        s = self._slot
        st, r = s.store, s.row
        st.has_prev[r] = stats is not None
        if stats is None:
            return
        st.prev_ops[r] = [[getattr(c, f) for f in OP_FIELDS]
                          for c in (stats.read, stats.write)]
        st.prev_gauges[r] = (stats.dirty_bytes, stats.dirty_peak_bytes,
                             stats.inflight_peak)
        st.prev_cfg[r] = (stats.rpc_window_pages, stats.rpcs_in_flight,
                          stats.dirty_cache_mb)


class NodeCacheArbiter:
    """Stage-2 stats collector + cache tuner for all clients on one node.

    The arbitration is a collect -> allocate -> apply pipeline (mirroring
    the stage-1 observe/actuate split): :meth:`collect` extracts each
    member's stage factors as :class:`CacheDemand` rows, the allocation
    runs Algorithm 2 (scalar :func:`cache_allocation` here, or
    :func:`~repro.core.cache_tuner.cache_allocation_many` when a fleet
    batches many nodes into one call), and :meth:`apply` actuates the
    limits and resets boundary members' factors.

    ``deferred=True`` queues boundary events instead of retuning inline:
    a fleet controller drains every pending node's boundary once per step
    into a single batched allocation — so a node retunes at most once per
    step even when several members cross together. Inline (default) mode
    keeps the paper's per-client semantics: every crossing retunes
    immediately.
    """

    def __init__(self, spaces: CaratSpaces, node_budget_mb: Optional[float] = None,
                 deferred: bool = False):
        self.spaces = spaces
        self.node_budget_mb = node_budget_mb
        self.members: List["CaratController"] = []
        self.deferred = deferred
        self.pending = False
        self._crossed: List["CaratController"] = []

    def register(self, ctrl: "CaratController") -> None:
        self.members.append(ctrl)

    def budget(self) -> float:
        if self.node_budget_mb is not None:
            return self.node_budget_mb
        return self.spaces.cache_max * max(len(self.members), 1) * 0.75

    # --- collect / apply pipeline --------------------------------------------
    def collect(self) -> List[CacheDemand]:
        """Demand extraction: one row per member, in registration order.

        Passes each member's raw write-RPC volume as the factor-(3)
        weight — the allocator owns the (single) normalization.
        """
        return [CacheDemand(
            client_id=m.client_id,
            active=m.stage_factors.saw_activity,
            peak_cache_bytes=m.stage_factors.peak_cache_bytes,
            peak_inflight_bytes=m.stage_factors.peak_inflight_bytes,
            write_rpc_share=m.stage_factors.write_rpcs,
        ) for m in self.members]

    def collect_rows(self) -> tuple:
        """:meth:`collect` as five parallel field rows (member order) for
        ``CacheDemandBatch.from_rows`` — the fleet drain's fast path, which
        skips the per-member :class:`CacheDemand` objects."""
        ms = self.members
        return ([m.client_id for m in ms],
                [m.stage_factors.saw_activity for m in ms],
                [m.stage_factors.peak_cache_bytes for m in ms],
                [m.stage_factors.peak_inflight_bytes for m in ms],
                [m.stage_factors.write_rpcs for m in ms])

    def apply(self, alloc: Dict[int, int]) -> None:
        """Actuate an allocation and close out boundary members' stages."""
        for m in self.members:
            if m.client is not None and m.client_id in alloc:
                m.client.set_cache_limit(alloc[m.client_id])
            # Only clients at an inactive->active boundary have finished the
            # stage their factors describe; clients still mid-active-stage
            # keep accumulating toward their own next boundary. (Deferred
            # crossings have already cleared their flag, hence _crossed.)
            if m.was_inactive_long or m in self._crossed:
                m.stage_factors.clear()
        self._crossed.clear()
        self.pending = False

    def apply_slots(self, values: Sequence[int]) -> None:
        """:meth:`apply` from a positional allocation row (slot order =
        member order, as produced by :meth:`collect_rows` + the batched
        allocator); padding beyond the member count is ignored."""
        for m, v in zip(self.members, values):
            if m.client is not None:
                m.client.set_cache_limit(v)
            if m.was_inactive_long or m in self._crossed:
                m.stage_factors.clear()
        self._crossed.clear()
        self.pending = False

    @property
    def crossings(self) -> int:
        """Members queued at a boundary since the last (deferred) apply."""
        return len(self._crossed)

    def mark_boundary(self, member: "CaratController") -> None:
        """A member hit its inactive->active boundary: retune now (inline
        mode) or queue for the fleet's end-of-step drain (deferred)."""
        if self.deferred:
            self.pending = True
            self._crossed.append(member)
        else:
            self.retune()

    def retune(self) -> Dict[int, int]:
        """Scalar compatibility path: collect -> Algorithm 2 -> apply."""
        alloc = cache_allocation(self.collect(), self.spaces, self.budget())
        self.apply(alloc)
        return alloc


class CaratController:
    """One CARAT instance, attached to one I/O client."""

    def __init__(
        self,
        client_id: int,
        spaces: CaratSpaces,
        models: Dict[str, object],          # op -> predict_proba callable
        cfg: Optional[CaratConfig] = None,
        rng: Optional[RngStream] = None,
        arbiter: Optional[NodeCacheArbiter] = None,
        slot: Optional[_Slot] = None,
    ):
        self.client_id = client_id
        self.cfg = cfg or CaratConfig()
        self.spaces = spaces
        # the row this shell's observe state lives at (a fresh one, or a
        # one-row store of its own); its builder shares it
        self._slot = slot if slot is not None else _Slot(
            ControllerStore(1, self.cfg.history_k), 0)
        self.builder = _ShellBuilder(self._slot,
                                     interval_s=self.cfg.probe_interval_s,
                                     history_k=self.cfg.history_k)
        probs = {op: (m.predict_proba if hasattr(m, "predict_proba") else m)
                 for op, m in models.items()}
        self.tuner: _TunerBase = make_tuner(
            self.cfg.tuner, spaces, probs, tau=self.cfg.prob_tau,
            alpha=self.cfg.alpha, beta=self.cfg.beta,
            epsilon=self.cfg.epsilon,
            rng=rng or RngStream(client_id, "carat"))
        self.arbiter = arbiter
        if arbiter is not None:
            arbiter.register(self)
        # the stage machine, the phase-change re-probing state and the
        # stage factors start at the row's defaults (see the properties)
        self.client: Optional[IOClient] = None
        # Table VIII accounting
        self.apply_time_total = 0.0
        self.apply_count = 0
        self.decisions: List[tuple] = []

    # --- state at the shell's row ------------------------------------------
    # stage machine
    inactive_s = _RowField("inactive_s", float)
    was_inactive_long = _RowField("was_inactive_long", bool)
    # phase-change re-probing state (replayed/dynamic workloads)
    _last_reprobe_t = _RowField("last_reprobe_t", float)
    _reprobe_pending = _RowField("reprobe_pending", bool)
    _bootstrap_pending = _RowField("bootstrap_pending", bool)

    @property
    def _last_sig(self) -> Optional[_AppSignature]:
        s = self._slot
        if not s.store.has_sig[s.row]:
            return None
        share, rr, rw = s.store.sig[s.row].tolist()
        return _AppSignature(read_share=share,
                             req_read=None if np.isnan(rr) else rr,
                             req_write=None if np.isnan(rw) else rw)

    @_last_sig.setter
    def _last_sig(self, sig: Optional[_AppSignature]) -> None:
        s = self._slot
        s.store.has_sig[s.row] = sig is not None
        if sig is not None:
            s.store.sig[s.row] = [sig.read_share] + [
                np.nan if v is None else v
                for v in (sig.req_read, sig.req_write)]

    @property
    def stage_factors(self) -> _StageFactors:
        return _StageFactors(self._slot)

    @stage_factors.setter
    def stage_factors(self, value: _StageFactors) -> None:
        mine = _StageFactors(self._slot)
        for f in _StageFactors.FIELDS:
            setattr(mine, f, getattr(value, f))

    # --- Simulation controller interface ---------------------------------------
    def observe(self, client: IOClient, t: float,
                dt: float) -> Optional[tuple]:
        """Snapshot + stage bookkeeping, *without* deciding.

        Runs everything up to (and including) the stage-2 boundary check,
        and returns ``(op, feats)`` when a stage-1 RPC decision is due —
        the hook a fleet controller uses to gather one batch across many
        clients. Returns None when no decision is needed this probe.
        """
        self.client = client
        snap = self.builder.sample(client.stats, t)
        if snap is None:
            return None
        self.stage_factors.update(snap)

        if not snap.active:
            # I/O-inactive: no RPC transfers, so RPC tuning is disabled
            self.inactive_s += dt
            if self.inactive_s >= self.cfg.inactive_threshold_s:
                self.was_inactive_long = True
            return None

        # I/O resumed after a long-enough inactive stage: stage-2 boundary
        if self.was_inactive_long and self.arbiter is not None:
            self.arbiter.mark_boundary(self)
        self.was_inactive_long = False
        self.inactive_s = 0.0

        # phase-change re-probe: the tuner's model is only confident near
        # the default config (it was trained on random excursions from
        # it), so a workload shift observed at a tuned config would leave
        # it silent below tau forever. Detect the shift from the
        # config-independent app signature and reset RPC params to the
        # space default — the next probes re-tune from the model's
        # confident region (IOPathTune/DIAL-style change response).
        if self.cfg.reprobe_on_change:
            sig = _AppSignature.of(snap)
            prev_sig, self._last_sig = self._last_sig, sig
            if (prev_sig is not None
                    and sig.changed_from(prev_sig,
                                         self.cfg.reprobe_req_ratio)):
                # deferred, not dropped: a change detected mid-cooldown
                # still re-probes once the cooldown expires
                self._reprobe_pending = True
            if (self._reprobe_pending and t - self._last_reprobe_t
                    >= self.cfg.reprobe_cooldown_s):
                self._reprobe_pending = False
                self._last_reprobe_t = t
                self._bootstrap_pending = True
                default = (self.spaces.default_rpc_window,
                           self.spaces.default_in_flight)
                if (client.config.rpc_window_pages,
                        client.config.rpcs_in_flight) != default:
                    client.set_rpc_config(*default)
                    self.decisions.append((t, "reprobe") + default)
                    rec = _telemetry()
                    if rec.enabled:
                        rec.count("carat.reprobe")
                    return None
                # already at default: fall through — this probe's features
                # were measured at default, so bootstrap right away

        # stage-1 RPC tuning, every probe interval
        op = snap.dominant_op
        feats = self.builder.feature_vector(op)
        if feats is None:
            return None
        if self._bootstrap_pending:
            # first probe after a re-probe reset: the model ranks regimes
            # well but calibrates conservatively away from its training
            # distribution, so the tau gate alone can leave a fresh phase
            # untuned. Take one tau-free greedy pick (scalar inference in
            # both the per-client and fleet paths, so decisions stay
            # bit-identical); every later probe is tau-gated as usual.
            self._bootstrap_pending = False
            probs = self.tuner._probs(op, feats)
            w, f = self.spaces.rpc_candidates()[int(np.argmax(probs))]
            self.client.set_rpc_config(w, f)
            self.decisions.append((t, "bootstrap", w, f))
            rec = _telemetry()
            if rec.enabled:
                rec.count("carat.bootstrap")
            return None
        rec = _telemetry()
        if rec.enabled:
            rec.count("carat.probe")
        return op, feats

    def actuate(self, op: str, proposal: Optional[tuple], t: float,
                tune_time_s: float = 0.0) -> None:
        """Apply a stage-1 decision produced for this controller's client.

        ``tune_time_s`` is the (share of) tuner time spent producing the
        proposal, folded into the Table VIII end-to-end accounting.
        """
        t0 = perf_s()
        if proposal is not None:
            self.client.set_rpc_config(*proposal)
            self.decisions.append((t, op) + tuple(proposal))
        self.apply_time_total += tune_time_s + perf_s() - t0
        self.apply_count += 1

    def __call__(self, client: IOClient, t: float, dt: float) -> None:
        pending = self.observe(client, t, dt)
        if pending is None:
            return
        op, feats = pending
        t0 = perf_s()
        proposal = self.tuner.propose(op, feats)
        self.actuate(op, proposal, t, perf_s() - t0)

    # --- Table VIII ----------------------------------------------------------
    def overheads(self) -> Dict[str, float]:
        return {
            "snapshot_ms": self.builder.mean_snapshot_time_s * 1e3,
            "inference_ms": self.tuner.mean_inference_s * 1e3,
            "end_to_end_ms": (self.builder.mean_snapshot_time_s
                              + self.apply_time_total
                              / max(self.apply_count, 1)) * 1e3,
        }


# What ControllerStore.probe reproduces: shells whose class observes
# otherwise (a subclass's override) are observed one by one.
STOCK_OBSERVE = CaratController.observe
