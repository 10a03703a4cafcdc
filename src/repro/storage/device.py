"""Device-resident fleet stepping for the ``soa-jax`` backend.

:class:`~repro.storage.soa.SoACore` with ``xp="jax"`` runs its
elementwise plan/commit math through ``jnp`` but keeps every carried
array host-side, round-tripping the whole fleet state twice per
interval and serializing in the cluster resolve. This module closes
that gap:

* :class:`DeviceFleet` keeps all per-client state *and* the per-OST
  cluster state as one jax pytree on a device across intervals, and
  fuses plan + resolve + commit into a single ``jit``-compiled step
  with the input state buffers **donated** — no host round-trip per
  phase, and (XLA willing) in-place buffer reuse across intervals.
* The per-OST resolve runs as segment reductions of per-channel demand
  lanes over OST ids (a dense one-hot contraction — XLA's CPU scatter
  serializes, the gemm path doesn't) — sufficient statistics
  (``Σwindow, Σrate, Σrate·pages, Σpages, count``) replace the host
  path's per-demand fold. This *reassociates* float sums, which is
  exactly the ``soa-jax`` tolerance contract (the bit-identical ``soa``
  backend keeps its sequential :class:`~repro.storage.pfs._SegmentFold`).
* :class:`ShardedDeviceFleet` maps sharded-runtime shards onto
  devices: each shard's client rows live on their own device, per-shard
  plan jits emit the (5, n_osts) demand partials, the partials merge
  **on the primary device** before the one globally-coupled resolve,
  and the broadcast feedback commits shard-locally.

Two host touchpoints remain by design. The OST service noise comes
from the cluster's NumPy RNG stream (so host and device paths stay on
the *same* RNG trajectory — one lognormal per active OST in ascending
id order); because the fused step needs the noise as an input, each
step also returns the **predicted next-interval OST-activity mask**
(derived from post-commit dirty state and ``active(t+dt)``), so the
host draws next interval's noise without pulling fleet state back.
Second, the plan-term statics: rather than baking them into the traced
closure as literals (which would bloat the XLA program at 10⁶
clients), they ride as device-resident pytree *arguments* — a
workload/config **value** mutation re-uploads them with unchanged
shapes (cache hit, no retrace), while a channel-layout change alters
input shapes and retraces exactly once. ``DeviceFleet.n_traces``
counts retraces for the jit-stability tests.

Telemetry: each host-side part of a step runs in a span of the
``fleet.`` vocabulary (``fleet.step``, ``fleet.push``, ``fleet.statics``,
``fleet.mask``, ``fleet.noise``, ``fleet.sync_host``), and an enabled
recorder counts the bytes every host<->device transfer moves
(``fleet.h2d_bytes``, ``fleet.d2h_bytes``). Device-to-device copies
between shards are not counted.

Ownership: whichever fleet last stepped owns the truth. Host-side
reads go through :meth:`SoACore.ensure_host` (lazy pull); host-side
state writes mark the device copy stale and the next device step
re-uploads. jax stays a soft dependency — importing this module
without jax installed raises the same actionable error as
``backend="soa-jax"``.

The fused-step promise is lint-enforced: ``caratlint`` rule CL004
flags host round-trips, Python control flow on traced values, and
donated-buffer reuse in this module (see ``CONTRIBUTING.md`` for the
rule catalogue and suppression syntax).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.runtime.telemetry.recorder import active as _telemetry
from repro.storage.params import PAGE_SIZE, PFSParams
from repro.storage.pfs import PFSCluster
from repro.storage.soa import OP_FIELDS, SoACore, resolve_xp

jnp = resolve_xp("jax")          # actionable ImportError when jax is absent
import jax                       # noqa: E402  (guarded by resolve_xp above)

_PAGE = float(PAGE_SIZE)

# _Static fields shipped to the device (everything plan/commit reads)
STATIC_FIELDS = (
    "ch_ost", "ch_valid", "W", "F", "C", "R", "req_g", "inplace", "think",
    "is_read", "is_mixed", "is_seq", "is_strided", "is_rand",
    "duty_pos", "duty_full", "period_g", "dxp",
    "lam_rate_w", "hot_bytes", "run", "p_eff_strided", "n_extents",
    "form_scan", "rb_sl", "depth", "lam_r_per_ch", "rb_rd", "misfire",
    "waves", "s_here", "win_rd", "r_pages", "n_ch_f", "nic_per_ch",
)

OST_STATE_FIELDS = ("ost_wait", "ost_util", "ost_inflight",
                    "ost_served_bytes", "ost_served_rpcs")


def _nbytes(tree) -> int:
    """Bytes held by the arrays of a pytree (what a transfer moves)."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def _statics_current(core: SoACore, seen: int) -> bool:
    """Whether statics version ``seen`` is the core's current one, so
    ``_ensure_static`` would recompute nothing and nothing is uploaded."""
    return (core._layout_ok and core._static_ok
            and seen == core._static_version)


def _onehot_T(n_osts: int, ch_ost) -> np.ndarray:
    """(n_osts, n*kmax) f64 one-hot of the raveled channel->OST map.
    Precomputed host-side per statics refresh (it only changes when the
    layout or a workload mutates) and shipped as a static; costs
    n_osts*n*kmax f64 of device memory in exchange for dropping the
    per-step compare+convert from the segment reductions."""
    ids = np.asarray(ch_ost).ravel()
    return (np.arange(n_osts)[:, None] == ids[None, :]).astype(np.float64)


# ---------------------------------------------------------------------------
# traced building blocks (pure functions of pytrees; composed under jit)
# ---------------------------------------------------------------------------
def _duty_act(s: Dict, t):
    """(n,) bool duty-cycle activity at time ``t`` — the one periodic
    (and ``remainder``-heavy; f64 remainder is ~15x a multiply on CPU)
    term of the plan. Materialized behind an optimization barrier so the
    XLA fuser computes it once instead of re-deriving the remainder
    inside every consumer fusion."""
    act = s["duty_pos"] & (s["duty_full"]
                           | (jnp.mod(t, s["period_g"]) < s["dxp"]))
    return jax.lax.optimization_barrier(act)


def _plan_terms(p: PFSParams, s: Dict, dirty, last_drain, ost_wait, t, dt,
                act=None):
    """The fused twin of ``SoACore.plan`` (same expressions, jnp-traced).

    ``ost_wait`` is the (n_osts,) smoothed queue delay — under full-fleet
    stepping every client's waits row equals it, so the per-client
    ``waits`` matrix collapses to one vector on device. ``act`` takes
    the precomputed duty activity for ``t`` (the fused step threads last
    interval's prediction through); default recomputes it.
    """
    if act is None:
        act = _duty_act(s, t)
    is_read = s["is_read"]
    planned = act | (dirty > 0.0)
    has_write = planned & (~is_read | (dirty > 0.0))
    drain_only = planned & is_read & (dirty > 0.0)
    has_read = planned & act & (is_read | s["is_mixed"])
    w_stream_active = act & ~is_read

    Wf, Ff, R = s["W"], s["F"], s["R"]
    n_ch_f, nic_per_ch = s["n_ch_f"], s["nic_per_ch"]
    wait_ch = ost_wait[s["ch_ost"]]                      # (n, kmax)

    # ---- write plan ----
    lam_req = jnp.where(w_stream_active, s["lam_rate_w"], 0.0)
    lam_bytes_w = lam_req * R
    absorb_frac = s["inplace"] * jnp.minimum(1.0, dirty / s["hot_bytes"])
    lam_pages = jnp.maximum(last_drain, lam_bytes_w * 0.25) / PAGE_SIZE
    density = (lam_pages * p.extent_timeout_s) / s["n_extents"]
    p_eff_random = jnp.minimum(Wf, jnp.maximum(s["run"], density))
    seq_like = drain_only | s["is_seq"]
    p_eff = jnp.where(seq_like, Wf,
                      jnp.where(s["is_strided"], s["p_eff_strided"],
                                p_eff_random))
    fill_frac = p_eff / Wf
    new_dirty_est = jnp.maximum(last_drain,
                                (lam_bytes_w * (1.0 - absorb_frac)) * 0.25)
    parked = (new_dirty_est * p.extent_timeout_s) * (1.0 - fill_frac)
    open_extents = parked / jnp.maximum(p_eff * PAGE_SIZE, 1.0)
    frag_commit = ((open_extents * Wf) * _PAGE) * p.frag_overhead
    C = s["C"]
    c_eff = jnp.maximum(C - frag_commit, 0.1 * C)
    timeout_occ = jnp.minimum(parked, 0.8 * c_eff)
    headroom = jnp.maximum((c_eff - dirty) - timeout_occ, 0.0)
    admit_cap = ((last_drain + headroom / dt)
                 / jnp.maximum(1.0 - absorb_frac, 1e-3))
    admit_floor = (0.05 * c_eff) / dt
    admitted = jnp.minimum(lam_bytes_w, jnp.maximum(admit_cap, admit_floor))
    absorbed = admitted * absorb_frac
    new_dirty_rate = admitted - absorbed
    rpc_bytes_w = p_eff * PAGE_SIZE
    form_cost = (1.0 - fill_frac) * s["form_scan"] + 30e-6
    form_bytes_cap = rpc_bytes_w / form_cost
    per_ch_backlog = (dirty / dt + new_dirty_rate) / n_ch_f
    rb_w = rpc_bytes_w[:, None]
    t_rpc_w = (((p.net_rtt_s + wait_ch) + p.ost_fixed_cpu_s)
               + rb_w / p.ost_disk_bw) + rb_w / p.nic_bw
    window_cap = (Ff[:, None] * rb_w) / t_rpc_w
    offer = jnp.minimum(
        jnp.minimum(jnp.minimum(per_ch_backlog[:, None], window_cap),
                    nic_per_ch[:, None]),
        (form_bytes_cap / n_ch_f)[:, None])
    w_rate = offer / rb_w
    w_window = jnp.minimum(Ff[:, None], (offer * t_rpc_w) / rb_w + 0.01)

    # ---- read plan ----
    rb_sl = s["rb_sl"][:, None]
    t_rpc_sl = (((p.net_rtt_s + wait_ch) + p.ost_fixed_cpu_s)
                + rb_sl / p.ost_disk_bw) + rb_sl / p.nic_bw
    depth = s["depth"]
    cap_sl = jnp.minimum(
        jnp.minimum((depth * rb_sl) / t_rpc_sl, nic_per_ch[:, None]),
        s["lam_r_per_ch"][:, None])
    rate_sl = cap_sl / rb_sl
    win_sl = jnp.minimum(depth, (cap_sl * t_rpc_sl) / rb_sl + 0.01)
    rb_rd = s["rb_rd"][:, None]
    t_rpc_rd = (((p.net_rtt_s + wait_ch) + p.ost_fixed_cpu_s)
                + rb_rd / p.ost_disk_bw) + rb_rd / p.nic_bw
    t_req = ((t_rpc_rd * s["waves"][:, None] + s["misfire"][:, None])
             + p.syscall_s) + s["think"][:, None]
    cap_rd = jnp.minimum((s["s_here"] * R[:, None]) / t_req,
                         nic_per_ch[:, None])
    rate_rd = cap_rd / rb_rd
    is_rand2 = s["is_rand"][:, None]
    return {
        "act": act, "has_write": has_write, "has_read": has_read,
        "p_eff": p_eff, "w_rate": w_rate, "w_window": w_window,
        "admitted": admitted, "absorbed": absorbed,
        "new_dirty_rate": new_dirty_rate, "lam_bytes_w": lam_bytes_w,
        "r_rate": jnp.where(is_rand2, rate_rd, rate_sl),
        "r_window": jnp.where(is_rand2, s["win_rd"], win_sl),
    }


def _segment_reduce(onehot_T, lanes_2d):
    """Per-OST sums of k lane vectors (length L): (k, n_osts).

    XLA's CPU scatter (``segment_sum``) serializes, and a broadcast
    masked reduce tempts the fuser into recomputing the whole lane
    pipeline once per OST row. A matvec per lane against the host-
    precomputed transposed one-hot OST matrix (``s["onehot_T"]``,
    (n_osts, L) f64 — the channel->OST map is static between layout
    changes, so building it in-step wasted a compare+convert over
    n_osts*L elements every interval) sidesteps both: lanes materialize
    exactly once and the contraction streams the one-hot rows
    sequentially."""
    return jnp.stack([onehot_T @ ln for ln in lanes_2d])


def _demand_partials(s: Dict, terms: Dict):
    """(5, n_osts) per-OST sufficient statistics of the offered demands:
    [Σwindow, Σrate, Σrate·pages, Σpages, count]. Linear in the demand
    lanes, so sharded partials merge by addition."""
    ch_valid = s["ch_valid"]
    wv = terms["has_write"][:, None] & ch_valid
    rv = terms["has_read"][:, None] & ch_valid
    wp = terms["p_eff"][:, None]
    rp = s["r_pages"][:, None]

    def lanes(w_x, r_x):
        # write and read lanes land on the same ids and sum linearly, so
        # they merge elementwise *before* the per-OST reduction
        return (jnp.where(wv, w_x, 0.0) + jnp.where(rv, r_x, 0.0)).ravel()

    one = jnp.ones(())
    return _segment_reduce(s["onehot_T"], [
        lanes(terms["w_window"], terms["r_window"]),
        lanes(terms["w_rate"], terms["r_rate"]),
        lanes(terms["w_rate"] * wp, terms["r_rate"] * rp),
        lanes(wp, rp),
        lanes(one, one),
    ])


def _resolve(p: PFSParams, ost: Dict, partials, noise, dt):
    """The fused twin of ``PFSCluster.resolve_batch`` over the merged
    per-OST sufficient statistics (algebraically equal to the per-demand
    fold; reassociated — the soa-jax tolerance contract)."""
    sum_win, sum_rate, sum_rp, sum_pages, cnt = partials
    nonempty = cnt > 0.0
    over = jnp.maximum(0.0, sum_win / p.ost_overload_knee - 1.0)
    fixed_eff = p.ost_fixed_cpu_s * (1.0 + p.ost_overload_gamma * over)
    qd = jnp.maximum(sum_win, 1.0)
    disk_bw = (p.ost_disk_bw * qd / (qd + p.ssd_qd_half)) / noise
    byte_rate = sum_rp * _PAGE
    util = fixed_eff * sum_rate + (_PAGE / disk_bw) * sum_rp
    util = jnp.maximum(util, byte_rate / p.ost_ingress_bw)
    # empty lanes divide by 1.0, not 0 — keeps infs/NaNs out of the graph
    safe_util = jnp.where(nonempty, util, 1.0)
    scale = jnp.where(util <= 0.95, 1.0, 0.95 / safe_util)
    rho = jnp.minimum(util * scale, 0.95)
    svc_avg = fixed_eff + (_PAGE / disk_bw) * (sum_pages
                                               / jnp.maximum(cnt, 1.0))
    wait_now = jnp.minimum(p.queue_wait_cap_s,
                           svc_avg * rho / jnp.maximum(1.0 - rho, 0.05))
    wait_now = jnp.where(util > 1.0, p.queue_wait_cap_s, wait_now)
    a = p.queue_smoothing
    new_wait = jnp.where(nonempty,
                         a * ost["ost_wait"] + (1 - a) * wait_now,
                         ost["ost_wait"] * 0.25)
    scale_out = jnp.where(nonempty, scale, 1.0)
    ost_out = {
        "ost_wait": new_wait,
        "ost_util": jnp.where(nonempty, util, 0.0),
        "ost_inflight": jnp.where(nonempty, sum_win, 0.0),
        "ost_served_bytes": (ost["ost_served_bytes"]
                             + (byte_rate * scale_out) * dt),
        "ost_served_rpcs": (ost["ost_served_rpcs"]
                            + (sum_rate * scale_out) * dt),
    }
    return ost_out, scale_out, new_wait


def _commit(p: PFSParams, s: Dict, state: Dict, terms: Dict,
            scale_out, new_wait, dt):
    """The fused twin of ``SoACore.commit`` for the client-side state.
    Channel sums reduce with ``.sum(axis=1)`` (reassociated — device
    tolerance path; the host backend keeps its sequential column loop).
    Returns the new client state dict."""
    ch_ost, ch_valid = s["ch_ost"], s["ch_valid"]
    dirty = state["dirty"]
    scale_ch = scale_out[ch_ost]
    wait_ch = new_wait[ch_ost]

    def channel_sums(rate, pages_1d):
        rb = pages_1d * PAGE_SIZE
        rb2 = rb[:, None]
        t_rpc = (((p.net_rtt_s + wait_ch) + p.ost_fixed_cpu_s)
                 + rb2 / p.ost_disk_bw) + rb2 / p.nic_bw
        ach = jnp.where(ch_valid, rate * scale_ch, 0.0)
        trm = jnp.where(ch_valid, t_rpc, 0.0)
        byte_sum = (ach * rb2).sum(axis=1)
        inflight = (ach * trm).sum(axis=1)
        lat_sum = ((ach * dt) * trm).sum(axis=1)
        rpcs = (ach * dt).sum(axis=1)
        pages_sum = ((ach * dt) * rb2 / PAGE_SIZE).sum(axis=1)
        n_live = (ch_valid & (rate > 0.0)).sum(axis=1).astype(byte_sum.dtype)
        return byte_sum, inflight, lat_sum, rpcs, pages_sum, n_live

    def bump(cur, mask, val):
        return cur + jnp.where(mask, val, 0.0)

    hw, hr, act = terms["has_write"], terms["has_read"], terms["act"]

    # ---- write commit ----
    (drained, inflight_w, lat_w, rpcs_w, _,
     live_w) = channel_sums(terms["w_rate"], terms["p_eff"])
    drained = jnp.minimum(drained, dirty / dt + terms["new_dirty_rate"])
    admitted, absorbed = terms["admitted"], terms["absorbed"]
    C = s["C"]
    new_dirty = dirty + ((admitted - absorbed) - drained) * dt
    over = new_dirty > C
    overflow = new_dirty - C
    af2 = absorbed / jnp.maximum(admitted, 1e-9)
    shrink = jnp.minimum(overflow / jnp.maximum(1.0 - af2, 1e-3),
                         admitted * dt)
    adm2 = jnp.maximum(admitted - shrink / dt, 0.0)
    abs2 = adm2 * af2
    nd2 = jnp.minimum(dirty + ((adm2 - abs2) - drained) * dt, C)
    blk2 = jnp.minimum(dt, overflow / jnp.maximum(terms["lam_bytes_w"], 1.0))
    admitted = jnp.where(over, adm2, admitted)
    absorbed = jnp.where(over, abs2, absorbed)
    new_dirty = jnp.maximum(jnp.where(over, nd2, new_dirty), 0.0)
    blocked = jnp.where(over, blk2, 0.0)

    dirty_out = jnp.where(hw, new_dirty, dirty)
    wr = state["write"]
    write_out = {
        "app_bytes": bump(wr["app_bytes"], hw, admitted * dt),
        "app_requests": bump(wr["app_requests"], hw,
                             (admitted * dt) / s["req_g"]),
        "rpc_count": bump(wr["rpc_count"], hw, rpcs_w),
        "rpc_pages": bump(wr["rpc_pages"], hw, (drained * dt) / PAGE_SIZE),
        "rpc_bytes": bump(wr["rpc_bytes"], hw, drained * dt),
        "lat_sum_s": bump(wr["lat_sum_s"], hw, lat_w),
        "inflight_time": bump(wr["inflight_time"], hw, inflight_w * dt),
        "channel_time": bump(wr["channel_time"], hw, live_w * dt),
        "absorbed_bytes": bump(wr["absorbed_bytes"], hw, absorbed * dt),
        "blocked_s": bump(wr["blocked_s"], hw, blocked),
        "active_s": bump(wr["active_s"], hw & act, dt),
    }
    ip = state["inflight_peak"]
    ip = jnp.where(hw, jnp.maximum(ip, inflight_w), ip)

    # ---- read commit ----
    (delivered, inflight_r, lat_r, rpcs_r, pages_r,
     live_r) = channel_sums(terms["r_rate"], s["r_pages"])
    rd = state["read"]
    read_out = {
        "app_bytes": bump(rd["app_bytes"], hr, delivered * dt),
        "app_requests": bump(rd["app_requests"], hr,
                             (delivered * dt) / s["req_g"]),
        "rpc_count": bump(rd["rpc_count"], hr, rpcs_r),
        "rpc_pages": bump(rd["rpc_pages"], hr, pages_r),
        "rpc_bytes": bump(rd["rpc_bytes"], hr, delivered * dt),
        "lat_sum_s": bump(rd["lat_sum_s"], hr, lat_r),
        "inflight_time": bump(rd["inflight_time"], hr, inflight_r * dt),
        "channel_time": bump(rd["channel_time"], hr, live_r * dt),
        "absorbed_bytes": rd["absorbed_bytes"],
        "blocked_s": rd["blocked_s"],
        "active_s": bump(rd["active_s"], hr, dt),
    }
    ip = jnp.where(hr, jnp.maximum(ip, inflight_r), ip)

    return {
        "dirty": dirty_out,
        "last_drain": jnp.where(hw, drained, state["last_drain"]),
        "read": read_out,
        "write": write_out,
        "dirty_peak": jnp.maximum(state["dirty_peak"], dirty_out),
        "inflight_peak": ip,
    }


def _activity_lanes(s: Dict, dirty, act):
    """Which clients offer demands given ``dirty`` state and the duty
    activity ``act`` for the interval — the exact condition under which
    ``PlanBatch.demand_batch`` emits a lane (and therefore under which
    the host resolver draws OST noise)."""
    planned = act | (dirty > 0.0)
    has_write = planned & (~s["is_read"] | (dirty > 0.0))
    has_read = planned & act & (s["is_read"] | s["is_mixed"])
    return has_write | has_read


def _activity_mask(s: Dict, dirty, act):
    """(n_osts,) bool: OSTs receiving >=1 demand lane this interval."""
    lanes = (_activity_lanes(s, dirty, act)[:, None] & s["ch_valid"]).ravel()
    cnt = _segment_reduce(s["onehot_T"], [lanes.astype(dirty.dtype)])
    return cnt[0] > 0.0


# ---------------------------------------------------------------------------
# single-device fused fleet
# ---------------------------------------------------------------------------
class DeviceFleet:
    """Device-resident full-fleet stepping for ``Simulation(backend="soa-jax")``.

    One fused, donated, jit-compiled ``step`` advances the whole fleet an
    interval entirely on-device; the only per-step host traffic is the
    OST noise draw in (n_osts,) and the predicted activity mask out.
    """

    def __init__(self, core: SoACore, cluster: PFSCluster,
                 device=None):
        self.core = core
        self.cluster = cluster
        self.device = device if device is not None else jax.devices()[0]
        self.host_stale = False      # host arrays lag the device state
        self.device_stale = True     # device copy lags the host arrays
        self.n_traces = 0            # fused-step retrace count (tests)
        self._state = None
        self._statics = None
        self._static_seen = -1
        self._wl_seen = -1
        self._mask: Optional[np.ndarray] = None
        self._step_fn = self._build_step()
        self._act_fn = jax.jit(_duty_act)
        self._mask_fn = jax.jit(
            lambda dirty, s, act: _activity_mask(s, dirty, act))

    # ------------------------------------------------------------- builders
    def _build_step(self):
        p = self.core.p

        def step(state, s, t, dt, noise):
            # Python side effect runs at trace time only — counts retraces
            self.n_traces += 1
            terms = _plan_terms(p, s, state["dirty"], state["last_drain"],
                                state["ost_wait"], t, dt, act=state["act"])
            # Materialize the plan terms before fanning them into the
            # demand reduction and commit: XLA's CPU fuser otherwise
            # duplicates the whole plan pipeline into every consumer.
            terms = jax.lax.optimization_barrier(terms)
            partials = _demand_partials(s, terms)
            ost_in = {f: state[f] for f in OST_STATE_FIELDS}
            ost_out, scale_out, new_wait = _resolve(p, ost_in, partials,
                                                    noise, dt)
            scale_out, new_wait = jax.lax.optimization_barrier(
                (scale_out, new_wait))
            client_out = _commit(p, s, state, terms, scale_out, new_wait, dt)
            new_state = {**client_out, **ost_out}
            # next interval's duty activity rides in the state pytree, so
            # the expensive periodic term is evaluated once per interval
            act_next = _duty_act(s, t + dt)
            new_state["act"] = act_next
            totals = new_state["read"]["app_bytes"] \
                + new_state["write"]["app_bytes"]
            mask_next = _activity_mask(s, new_state["dirty"], act_next)
            return new_state, totals, mask_next

        return jax.jit(step, donate_argnums=(0,))

    # ------------------------------------------------------- host <-> device
    def _host_state(self) -> Dict:
        core, cl = self.core, self.cluster
        return {
            "dirty": core.dirty_bytes, "last_drain": core.last_drain,
            "read": {f: getattr(core.read, f) for f in OP_FIELDS},
            "write": {f: getattr(core.write, f) for f in OP_FIELDS},
            "dirty_peak": core.dirty_peak_bytes,
            "inflight_peak": core.inflight_peak,
            "ost_wait": cl.wait_s, "ost_util": cl.utilization,
            "ost_inflight": cl.inflight,
            "ost_served_bytes": cl.served_bytes,
            "ost_served_rpcs": cl.served_rpcs,
            # placeholder — step() recomputes it on every fresh push
            # (the push clears the predicted mask, forcing that branch)
            "act": np.zeros(core.n, dtype=bool),
        }

    def _push(self) -> None:
        """Upload host state to the device (host stays valid until the
        next fused step marks it stale)."""
        rec = _telemetry()
        with rec.span("fleet.push", cat="fleet"):
            self._state = jax.device_put(self._host_state(), self.device)
            if rec.enabled:
                rec.count("fleet.h2d_bytes", _nbytes(self._state))
        self.device_stale = False
        self._mask = None            # dirty may have changed: recompute

    def _refresh_statics(self) -> None:
        core = self.core
        if _statics_current(core, self._static_seen):
            return
        rec = _telemetry()
        with rec.span("fleet.statics", cat="fleet"):
            core._ensure_static()
            st = core._static
            d = {f: np.asarray(getattr(st, f)) for f in STATIC_FIELDS}
            d["onehot_T"] = _onehot_T(core.p.n_osts, st.ch_ost)
            self._statics = jax.device_put(d, self.device)
            self._static_seen = core._static_version
            if rec.enabled:
                rec.count("fleet.h2d_bytes", _nbytes(self._statics))

    def sync_host(self) -> None:
        """Pull device state back into the core/cluster host arrays.
        The device copy remains authoritative (reads don't invalidate)."""
        rec = _telemetry()
        with rec.span("fleet.sync_host", cat="fleet"):
            h = jax.tree.map(np.asarray, self._state)
            core, cl = self.core, self.cluster
            core.dirty_bytes[:] = h["dirty"]
            core.last_drain[:] = h["last_drain"]
            # full-fleet contract: every client's waits row is the OST
            # vector
            core.waits[:, :] = h["ost_wait"][None, :]
            for f in OP_FIELDS:
                getattr(core.read, f)[:] = h["read"][f]
                getattr(core.write, f)[:] = h["write"][f]
            core.dirty_peak_bytes[:] = h["dirty_peak"]
            core.inflight_peak[:] = h["inflight_peak"]
            cl.wait_s[:] = h["ost_wait"]
            cl.utilization[:] = h["ost_util"]
            cl.inflight[:] = h["ost_inflight"]
            cl.served_bytes[:] = h["ost_served_bytes"]
            cl.served_rpcs[:] = h["ost_served_rpcs"]
            if rec.enabled:
                rec.count("fleet.d2h_bytes", _nbytes(h))
        self.host_stale = False

    def _take_ownership(self) -> None:
        """Become the core's device owner (syncing any previous owner's
        state through the host arrays first)."""
        core = self.core
        old = core._device
        if old is self:
            return
        if old is not None:
            if old.host_stale:
                old.sync_host()
            old.device_stale = True
        core._device = self
        self.device_stale = True

    # ----------------------------------------------------------------- step
    def step(self, t: float, dt: float):
        """Advance the fleet one interval on-device; returns the
        per-client cumulative read+write app_bytes as a *device* array
        (callers pull it only if they need the throughput series)."""
        rec = _telemetry()
        with rec.span("fleet.step", cat="fleet"):
            core = self.core
            self._take_ownership()
            if self.device_stale or self._state is None:
                self._push()
            self._refresh_statics()
            if self._mask is None or self._wl_seen != core._wl_version:
                # no valid predicted mask (fresh push or workload
                # mutation): recompute this interval's duty activity + OST
                # mask on-device (``act`` is computed there: its put is
                # no transfer)
                with rec.span("fleet.mask", cat="fleet"):
                    act = self._act_fn(self._statics, t)
                    self._state["act"] = jax.device_put(act, self.device)
                    self._mask = np.asarray(
                        self._mask_fn(self._state["dirty"], self._statics,
                                      act))
                    self._wl_seen = core._wl_version
                if rec.enabled:
                    rec.count("fleet.d2h_bytes", self._mask.nbytes)
            with rec.span("fleet.noise", cat="fleet"):
                noise = self.cluster._noise_for(self._mask)
            state, totals, mask_next = self._step_fn(
                self._state, self._statics, t, dt, noise)
            self._state = state
            self._mask = np.asarray(mask_next)
            self.host_stale = True
            if rec.enabled:
                rec.count("fleet.h2d_bytes", noise.nbytes)
                rec.count("fleet.d2h_bytes", self._mask.nbytes)
        return totals


# ---------------------------------------------------------------------------
# shard -> device mapping (sync sharded runtime)
# ---------------------------------------------------------------------------
class ShardedDeviceFleet:
    """Map sharded-runtime shards onto devices.

    Each shard's client rows live on ``devices[i % len(devices)]``; a
    per-shard plan jit emits the (5, n_osts) demand partials, partials
    merge by addition on the primary device before the one
    globally-coupled resolve, and the broadcast (scale, waits) feedback
    commits shard-locally. Noise comes from the same cluster RNG stream
    with the same draw pattern as every other resolver. Matches the
    single-device ``DeviceFleet`` within the soa-jax tolerance (the
    partial merge reassociates across shards).
    """

    def __init__(self, core: SoACore, cluster: PFSCluster,
                 shard_idx: Sequence[np.ndarray],
                 devices: Optional[Sequence] = None):
        self.core = core
        self.cluster = cluster
        devs = list(devices) if devices is not None else jax.devices()
        self.shard_idx = [np.asarray(ix, dtype=np.int64) for ix in shard_idx]
        self.devices = [devs[i % len(devs)]
                        for i in range(len(self.shard_idx))]
        self.primary = devs[0]
        self.host_stale = False
        self.device_stale = True
        self.n_traces = 0
        self._states: List[Dict] = []
        self._statics: List[Dict] = []
        self._ost_state = None
        self._static_seen = -1
        self._wl_seen = -1
        self._mask: Optional[np.ndarray] = None
        p = core.p

        def plan_fn(state, s, ost_wait, t, dt):
            self.n_traces += 1
            terms = _plan_terms(p, s, state["dirty"], state["last_drain"],
                                ost_wait, t, dt)
            return terms, _demand_partials(s, terms)

        def resolve_fn(ost, partials, noise, dt):
            return _resolve(p, ost, partials, noise, dt)

        def commit_fn(state, s, terms, scale_out, new_wait, dt):
            out = _commit(p, s, state, terms, scale_out, new_wait, dt)
            totals = out["read"]["app_bytes"] + out["write"]["app_bytes"]
            return out, totals

        def lanes_fn(s, dirty, t):
            act = _duty_act(s, t)
            lanes = (_activity_lanes(s, dirty, act)[:, None]
                     & s["ch_valid"]).ravel()
            return _segment_reduce(s["onehot_T"],
                                   [lanes.astype(dirty.dtype)])[0]

        self._plan_fn = jax.jit(plan_fn)
        self._resolve_fn = jax.jit(resolve_fn)
        self._commit_fn = jax.jit(commit_fn, donate_argnums=(0,))
        self._lanes_fn = jax.jit(lanes_fn)

    # ------------------------------------------------------- host <-> device
    def _push(self) -> None:
        core, cl = self.core, self.cluster
        rec = _telemetry()
        with rec.span("fleet.push", cat="fleet"):
            self._states = []
            for ix, dev in zip(self.shard_idx, self.devices):
                st = {
                    "dirty": core.dirty_bytes[ix],
                    "last_drain": core.last_drain[ix],
                    "read": {f: getattr(core.read, f)[ix]
                             for f in OP_FIELDS},
                    "write": {f: getattr(core.write, f)[ix]
                              for f in OP_FIELDS},
                    "dirty_peak": core.dirty_peak_bytes[ix],
                    "inflight_peak": core.inflight_peak[ix],
                }
                self._states.append(jax.device_put(st, dev))
            self._ost_state = jax.device_put(
                {"ost_wait": cl.wait_s, "ost_util": cl.utilization,
                 "ost_inflight": cl.inflight,
                 "ost_served_bytes": cl.served_bytes,
                 "ost_served_rpcs": cl.served_rpcs}, self.primary)
            if rec.enabled:
                rec.count("fleet.h2d_bytes", _nbytes(self._states)
                          + _nbytes(self._ost_state))
        self.device_stale = False
        self._mask = None

    def _refresh_statics(self) -> None:
        core = self.core
        if _statics_current(core, self._static_seen):
            return
        rec = _telemetry()
        with rec.span("fleet.statics", cat="fleet"):
            core._ensure_static()
            st = core._static
            self._statics = []
            for ix, dev in zip(self.shard_idx, self.devices):
                sl = {f: np.asarray(getattr(st, f))[ix]
                      for f in STATIC_FIELDS}
                sl["onehot_T"] = _onehot_T(core.p.n_osts,
                                           np.asarray(st.ch_ost)[ix])
                self._statics.append(jax.device_put(sl, dev))
            self._static_seen = core._static_version
            if rec.enabled:
                rec.count("fleet.h2d_bytes", _nbytes(self._statics))

    def sync_host(self) -> None:
        core, cl = self.core, self.cluster
        rec = _telemetry()
        with rec.span("fleet.sync_host", cat="fleet"):
            for ix, st in zip(self.shard_idx, self._states):
                h = jax.tree.map(np.asarray, st)
                core.dirty_bytes[ix] = h["dirty"]
                core.last_drain[ix] = h["last_drain"]
                for f in OP_FIELDS:
                    getattr(core.read, f)[ix] = h["read"][f]
                    getattr(core.write, f)[ix] = h["write"][f]
                core.dirty_peak_bytes[ix] = h["dirty_peak"]
                core.inflight_peak[ix] = h["inflight_peak"]
            ost = jax.tree.map(np.asarray, self._ost_state)
            core.waits[:, :] = ost["ost_wait"][None, :]
            cl.wait_s[:] = ost["ost_wait"]
            cl.utilization[:] = ost["ost_util"]
            cl.inflight[:] = ost["ost_inflight"]
            cl.served_bytes[:] = ost["ost_served_bytes"]
            cl.served_rpcs[:] = ost["ost_served_rpcs"]
            if rec.enabled:
                rec.count("fleet.d2h_bytes", _nbytes(self._states)
                          + _nbytes(self._ost_state))
        self.host_stale = False

    def _take_ownership(self) -> None:
        core = self.core
        old = core._device
        if old is self:
            return
        if old is not None:
            if old.host_stale:
                old.sync_host()
            old.device_stale = True
        core._device = self
        self.device_stale = True

    # ----------------------------------------------------------------- step
    def step(self, t: float, dt: float) -> List:
        """One barrier interval across all shard devices. Returns the
        per-shard cumulative read+write app_bytes device arrays (shard
        order), for the runtime's throughput accounting."""
        rec = _telemetry()
        with rec.span("fleet.step", cat="fleet"):
            return self._step(rec, t, dt)

    def _step(self, rec, t: float, dt: float) -> List:
        core = self.core
        self._take_ownership()
        if self.device_stale or self._ost_state is None:
            self._push()
        self._refresh_statics()

        # shard plans (dispatch per shard device; XLA runs them async)
        wait_vec = self._ost_state["ost_wait"]
        results = []
        for st, sl, dev in zip(self._states, self._statics, self.devices):
            w = wait_vec if dev == self.primary \
                else jax.device_put(wait_vec, dev)
            results.append(self._plan_fn(st, sl, w, t, dt))

        # merge demand partials on the primary device, in shard order
        merged = None
        for _, partials in results:
            part = jax.device_put(partials, self.primary)
            merged = part if merged is None else merged + part

        if self._mask is None or self._wl_seen != core._wl_version:
            with rec.span("fleet.mask", cat="fleet"):
                cnt = None
                for st, sl, dev in zip(self._states, self._statics,
                                       self.devices):
                    c = jax.device_put(self._lanes_fn(sl, st["dirty"], t),
                                       self.primary)
                    cnt = c if cnt is None else cnt + c
                cnt = np.asarray(cnt)
                self._mask = cnt > 0.0
                self._wl_seen = core._wl_version
            if rec.enabled:
                rec.count("fleet.d2h_bytes", cnt.nbytes)
        with rec.span("fleet.noise", cat="fleet"):
            noise = self.cluster._noise_for(self._mask)
        if rec.enabled:
            rec.count("fleet.h2d_bytes", noise.nbytes)

        ost_out, scale_out, new_wait = self._resolve_fn(
            self._ost_state, merged, noise, dt)
        self._ost_state = ost_out

        totals = []
        new_states = []
        for (terms, _), st, sl, dev in zip(results, self._states,
                                           self._statics, self.devices):
            sc = scale_out if dev == self.primary \
                else jax.device_put(scale_out, dev)
            nw = new_wait if dev == self.primary \
                else jax.device_put(new_wait, dev)
            out, tot = self._commit_fn(st, sl, terms, sc, nw, dt)
            new_states.append(out)
            totals.append(tot)
        self._states = new_states
        # next interval's activity depends on post-commit dirty: cheap
        # per-shard recompute next step (no prediction fused here)
        self._mask = None
        self.host_stale = True
        return totals
