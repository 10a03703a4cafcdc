"""Plain NumPy reference of one fleet interval (plan, resolve, commit).

An independent rendering of the interval-fluid Lustre client and OST
model (the CARAT paper §II-A mechanics as the repository's scalar client
states them), written over whole-fleet arrays. It imports nothing of the
program: it builds its plan constants from the workload fields of the
traffic file, the client configurations and the stripe offsets, and it
takes the fleet state before the interval and the OST service noise
stream's position as its inputs.

``dtype`` is the arithmetic precision: ``np.float64`` is the reference,
``np.float32`` the lower-precision control that must fail the check.
The per-OST sums use ``np.bincount`` (any association; the comparison
is a tolerance, not bit identity).
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

PAGE_SIZE = 4096.0
OP_FIELDS = ("app_bytes", "app_requests", "rpc_count", "rpc_pages",
             "rpc_bytes", "lat_sum_s", "inflight_time", "channel_time",
             "absorbed_bytes", "blocked_s", "active_s")


def member_arrays(members: Sequence[dict], member_idx: np.ndarray) -> Dict:
    """Per-client workload descriptor arrays from the traffic members."""
    def col(key, default, dtype=np.float64):
        vals = np.array([m.get(key, default) for m in members], dtype=dtype)
        return vals[member_idx]
    op = np.array([{"read": 0, "write": 1, "mixed": 2}[m["op"]]
                   for m in members])[member_idx]
    acc = np.array([{"seq": 0, "random": 1, "strided": 2}[m["access"]]
                    for m in members])[member_idx]
    return {"op": op, "access": acc,
            "req": col("req_bytes", 0.0), "streams": col("n_streams", 1,
                                                         np.int64),
            "file": col("file_bytes", float(1 << 30)),
            "inplace": col("inplace_frac", 0.0),
            "read_frac": col("read_frac", 0.0), "think": col("think_s", 0.0),
            "duty": col("duty_cycle", 1.0), "period": col("period_s", 1.0),
            "stride": col("stride_bytes", 0.0)}


def statics(p: Dict, wl: Dict, cfg_window, cfg_inflight, cfg_cache_mb,
            offsets, dtype=np.float64) -> Dict:
    """Plan constants of every client (layout, write and read terms)."""
    f = dtype
    n_osts = int(p["n_osts"])
    streams = wl["streams"]
    k = np.minimum(streams, n_osts)
    kmax = max(int(k.max()), 1)
    j = np.arange(kmax)[None, :]
    valid = j < k[:, None]
    ch_ost = np.where(valid, (np.asarray(offsets)[:, None] + j) % n_osts, 0)
    ch_streams = np.where(valid, (streams[:, None] - j - 1) // n_osts + 1, 0)
    n_ch = np.maximum(k, 1).astype(f)
    W = np.asarray(cfg_window).astype(f)
    F = np.asarray(cfg_inflight).astype(f)
    C = np.asarray(cfg_cache_mb).astype(f) * f(1024.0) * f(1024.0)
    R = wl["req"].astype(f)
    is_read, is_mixed = wl["op"] == 0, wl["op"] == 2
    is_seq, is_rand, is_strided = (wl["access"] == 0, wl["access"] == 1,
                                   wl["access"] == 2)
    think = wl["think"].astype(f)
    period = wl["period"].astype(f)
    duty = wl["duty"].astype(f)
    stride = np.where(wl["stride"] > 0, wl["stride"], 1.0).astype(f)
    file_b = wl["file"].astype(f)
    req_pages = np.maximum(f(1.0), np.ceil(R / f(PAGE_SIZE)))
    per_req = (f(p["syscall_s"]) + R / f(p["mem_bw"])) + think
    r_share = np.where(is_mixed, wl["read_frac"], 1.0).astype(f)
    w_share = np.where(is_mixed, 1.0 - wl["read_frac"], 1.0).astype(f)
    s_f = streams.astype(f)
    chs = ch_streams.astype(f)
    p_eff_sl = np.where(is_seq, W, np.minimum(req_pages, W))
    ra_frac = np.where(is_seq, f(1.0), np.minimum(R / stride, f(1.0)))
    rb_sl = p_eff_sl * f(PAGE_SIZE)
    p_eff_rd = np.minimum(req_pages, W)
    rpr = np.ceil(req_pages / W)
    run = np.minimum(req_pages, W)
    return {
        "ch_ost": ch_ost, "valid": valid, "W": W, "F": F, "C": C, "R": R,
        "req_g": np.maximum(R, f(1.0)), "inplace": wl["inplace"].astype(f),
        "think": think, "is_read": is_read, "is_mixed": is_mixed,
        "is_seq": is_seq, "is_rand": is_rand, "is_strided": is_strided,
        "duty_pos": duty > 0, "duty_full": duty >= 1,
        "period": np.where(period > 0, period, f(1.0)), "dxp": duty * period,
        "n_ch": n_ch, "nic_per_ch": f(p["nic_bw"]) / n_ch,
        "lam_rate_w": np.maximum(s_f * w_share, f(1e-6)) / per_req,
        "hot": np.maximum(R, file_b * f(0.10)), "run": run,
        "p_eff_strided": np.minimum(W, np.maximum(
            run, W * np.minimum(R / stride, f(1.0)))),
        "n_extents": np.maximum(file_b / (W * f(PAGE_SIZE)), f(1.0)),
        "form_scan": (W * f(PAGE_SIZE)) / f(p["extent_scan_bw"]),
        "rb_sl": rb_sl,
        "depth": np.minimum(F[:, None], (np.maximum(
            f(1.0), (f(p["readahead_bytes"]) * ra_frac) / rb_sl)[:, None]
            * chs) * r_share[:, None]),
        "lam_r_per_ch": ((np.maximum(s_f * r_share, f(1e-6)) / per_req) * R)
        / n_ch,
        "rb_rd": p_eff_rd * f(PAGE_SIZE),
        "misfire": f(p["ra_misfire_frac"]) * ((W * f(PAGE_SIZE))
                                              / f(p["ost_disk_bw"])),
        "waves": np.ceil(rpr / np.maximum(np.minimum(F, rpr), f(1.0))),
        "s_here": chs * r_share[:, None],
        "win_rd": np.minimum(F[:, None], rpr[:, None] * chs * r_share[:, None]),
        "r_pages": np.where(is_rand, p_eff_rd, p_eff_sl),
    }


def duty_active(s: Dict, t: float) -> np.ndarray:
    """Which clients are in the I/O-active part of their burst period."""
    f = s["W"].dtype.type
    return s["duty_pos"] & (s["duty_full"]
                            | (np.mod(f(t), s["period"]) < s["dxp"]))


def lanes_live(s: Dict, dirty: np.ndarray, act: np.ndarray):
    """(has_write, has_read) per client for an interval."""
    planned = act | (dirty > 0.0)
    has_write = planned & (~s["is_read"] | (dirty > 0.0))
    has_read = planned & act & (s["is_read"] | s["is_mixed"])
    return has_write, has_read


def ost_active(s: Dict, dirty: np.ndarray, act: np.ndarray,
               n_osts: int) -> np.ndarray:
    """OSTs that receive at least one demand lane this interval."""
    hw, hr = lanes_live(s, dirty, act)
    live = (hw | hr)[:, None] & s["valid"]
    return np.bincount(s["ch_ost"][live], minlength=n_osts) > 0


def noise_from(rng_state: dict, mask: np.ndarray, sigma: float) -> tuple:
    """One lognormal service-time factor per active OST, in ascending OST
    order, drawn from a PCG64 stream at ``rng_state``; returns the noise
    vector and the stream's state after the draw."""
    gen = np.random.Generator(np.random.PCG64(0))
    gen.bit_generator.state = rng_state
    noise = np.ones(mask.shape[0])
    k = int(np.count_nonzero(mask))
    if k:
        noise[mask] = gen.lognormal(0.0, sigma, size=k)
    return noise, gen.bit_generator.state


def step(p: Dict, s: Dict, state: Dict, t: float, dt: float,
         noise: np.ndarray) -> Dict:
    """One interval: returns the fleet state after it (same keys)."""
    f = s["W"].dtype.type
    cast = (lambda a: np.asarray(a).astype(f))
    dt = f(dt)
    PG = f(PAGE_SIZE)
    n_osts = int(p["n_osts"])
    dirty = cast(state["dirty"])
    last_drain = cast(state["last_drain"])
    ost_wait = cast(state["ost_wait"])
    act = duty_active(s, t)
    has_write, has_read = lanes_live(s, dirty, act)
    drain_only = (act | (dirty > 0.0)) & s["is_read"] & (dirty > 0.0)
    W, F, R, C = s["W"], s["F"], s["R"], s["C"]
    ch_ost, valid = s["ch_ost"], s["valid"]
    rtt, fixed = f(p["net_rtt_s"]), f(p["ost_fixed_cpu_s"])
    disk, nic = f(p["ost_disk_bw"]), f(p["nic_bw"])

    def t_rpc(wait_ch, rb2):
        return (((rtt + wait_ch) + fixed) + rb2 / disk) + rb2 / nic

    # ---- write plan ----
    wait_ch = ost_wait[ch_ost]
    lam_bytes = np.where(act & ~s["is_read"], s["lam_rate_w"], f(0.0)) * R
    absorb = s["inplace"] * np.minimum(f(1.0), dirty / s["hot"])
    lam_pages = np.maximum(last_drain, lam_bytes * f(0.25)) / PG
    density = (lam_pages * f(p["extent_timeout_s"])) / s["n_extents"]
    p_eff = np.where(drain_only | s["is_seq"], W,
                     np.where(s["is_strided"], s["p_eff_strided"],
                              np.minimum(W, np.maximum(s["run"], density))))
    fill = p_eff / W
    nd_est = np.maximum(last_drain, (lam_bytes * (f(1.0) - absorb))
                        * f(0.25))
    parked = (nd_est * f(p["extent_timeout_s"])) * (f(1.0) - fill)
    open_ext = parked / np.maximum(p_eff * PG, f(1.0))
    frag = ((open_ext * W) * PG) * f(p["frag_overhead"])
    c_eff = np.maximum(C - frag, f(0.1) * C)
    headroom = np.maximum((c_eff - dirty) - np.minimum(parked, f(0.8) * c_eff),
                          f(0.0))
    admit_cap = (last_drain + headroom / dt) / np.maximum(f(1.0) - absorb,
                                                          f(1e-3))
    admitted = np.minimum(lam_bytes, np.maximum(admit_cap,
                                                (f(0.05) * c_eff) / dt))
    absorbed = admitted * absorb
    new_dirty_rate = admitted - absorbed
    rb_w = p_eff * PG
    form_cap = rb_w / ((f(1.0) - fill) * s["form_scan"] + f(30e-6))
    backlog = (dirty / dt + new_dirty_rate) / s["n_ch"]
    tw = t_rpc(wait_ch, rb_w[:, None])
    offer = np.minimum(np.minimum(np.minimum(
        backlog[:, None], (F[:, None] * rb_w[:, None]) / tw),
        s["nic_per_ch"][:, None]), (form_cap / s["n_ch"])[:, None])
    w_rate = offer / rb_w[:, None]
    w_window = np.minimum(F[:, None], (offer * tw) / rb_w[:, None] + f(0.01))

    # ---- read plan ----
    rb_sl = s["rb_sl"][:, None]
    tsl = t_rpc(wait_ch, rb_sl)
    cap_sl = np.minimum(np.minimum((s["depth"] * rb_sl) / tsl,
                                   s["nic_per_ch"][:, None]),
                        s["lam_r_per_ch"][:, None])
    rb_rd = s["rb_rd"][:, None]
    t_req = ((t_rpc(wait_ch, rb_rd) * s["waves"][:, None]
              + s["misfire"][:, None]) + f(p["syscall_s"])) \
        + s["think"][:, None]
    cap_rd = np.minimum((s["s_here"] * R[:, None]) / t_req,
                        s["nic_per_ch"][:, None])
    rand = s["is_rand"][:, None]
    r_rate = np.where(rand, cap_rd / rb_rd, cap_sl / rb_sl)
    r_window = np.where(rand, s["win_rd"],
                        np.minimum(s["depth"], (cap_sl * tsl) / rb_sl
                                   + f(0.01)))

    # ---- resolve: per-OST sums over every live demand lane ----
    wv = has_write[:, None] & valid
    rv = has_read[:, None] & valid
    wp = np.broadcast_to(p_eff[:, None], wv.shape)
    rp = np.broadcast_to(s["r_pages"][:, None], rv.shape)
    ids = np.concatenate([ch_ost[wv], ch_ost[rv]])

    def per_ost(wx, rx):
        v = np.concatenate([np.broadcast_to(wx, wv.shape)[wv],
                            np.broadcast_to(rx, rv.shape)[rv]])
        return np.bincount(ids, weights=v, minlength=n_osts).astype(f)

    sum_win = per_ost(w_window, r_window)
    sum_rate = per_ost(w_rate, r_rate)
    sum_rp = per_ost(w_rate * wp, r_rate * rp)
    sum_pages = per_ost(wp, rp)
    cnt = np.bincount(ids, minlength=n_osts).astype(f)
    busy = cnt > 0
    over = np.maximum(f(0.0), sum_win / f(p["ost_overload_knee"]) - f(1.0))
    fixed_eff = fixed * (f(1.0) + f(p["ost_overload_gamma"]) * over)
    qd = np.maximum(sum_win, f(1.0))
    disk_bw = (disk * qd / (qd + f(p["ssd_qd_half"]))) / cast(noise)
    byte_rate = sum_rp * PG
    util = np.maximum(fixed_eff * sum_rate + (PG / disk_bw) * sum_rp,
                      byte_rate / f(p["ost_ingress_bw"]))
    scale = np.where(util <= f(0.95), f(1.0),
                     f(0.95) / np.where(busy, util, f(1.0)))
    rho = np.minimum(util * scale, f(0.95))
    svc = fixed_eff + (PG / disk_bw) * (sum_pages / np.maximum(cnt, f(1.0)))
    cap = f(p["queue_wait_cap_s"])
    wait_now = np.where(util > f(1.0), cap, np.minimum(
        cap, svc * rho / np.maximum(f(1.0) - rho, f(0.05))))
    a = f(p["queue_smoothing"])
    new_wait = np.where(busy, a * ost_wait + (f(1.0) - a) * wait_now,
                        ost_wait * f(0.25))
    scale = np.where(busy, scale, f(1.0))
    out = {
        "ost_wait": new_wait,
        "ost_util": np.where(busy, util, f(0.0)),
        "ost_inflight": np.where(busy, sum_win, f(0.0)),
        "ost_served_bytes": cast(state["ost_served_bytes"])
        + (byte_rate * scale) * dt,
        "ost_served_rpcs": cast(state["ost_served_rpcs"])
        + (sum_rate * scale) * dt,
    }

    # ---- commit ----
    scale_ch = scale[ch_ost]
    wait2 = new_wait[ch_ost]

    def channel_sums(rate, pages):
        rb2 = (pages * PG)[:, None]
        ach = np.where(valid, rate * scale_ch, f(0.0))
        trm = np.where(valid, t_rpc(wait2, rb2), f(0.0))
        return ((ach * rb2).sum(axis=1), (ach * trm).sum(axis=1),
                ((ach * dt) * trm).sum(axis=1), (ach * dt).sum(axis=1),
                ((ach * dt) * rb2 / PG).sum(axis=1),
                (valid & (rate > 0.0)).sum(axis=1).astype(f))

    drained, infl_w, lat_w, rpcs_w, _, live_w = channel_sums(w_rate, p_eff)
    drained = np.minimum(drained, dirty / dt + new_dirty_rate)
    new_dirty = dirty + ((admitted - absorbed) - drained) * dt
    over_c = new_dirty > C
    overflow = new_dirty - C
    af2 = absorbed / np.maximum(admitted, f(1e-9))
    shrink = np.minimum(overflow / np.maximum(f(1.0) - af2, f(1e-3)),
                        admitted * dt)
    adm2 = np.maximum(admitted - shrink / dt, f(0.0))
    abs2 = adm2 * af2
    nd2 = np.minimum(dirty + ((adm2 - abs2) - drained) * dt, C)
    blk2 = np.minimum(dt, overflow / np.maximum(lam_bytes, f(1.0)))
    admitted = np.where(over_c, adm2, admitted)
    absorbed = np.where(over_c, abs2, absorbed)
    new_dirty = np.maximum(np.where(over_c, nd2, new_dirty), f(0.0))
    blocked = np.where(over_c, blk2, f(0.0))

    def bump(cur, mask, val):
        return cast(cur) + np.where(mask, val, f(0.0))

    hw, hr = has_write, has_read
    wr, rd = state["write"], state["read"]
    out["write"] = {
        "app_bytes": bump(wr["app_bytes"], hw, admitted * dt),
        "app_requests": bump(wr["app_requests"], hw,
                             (admitted * dt) / s["req_g"]),
        "rpc_count": bump(wr["rpc_count"], hw, rpcs_w),
        "rpc_pages": bump(wr["rpc_pages"], hw, (drained * dt) / PG),
        "rpc_bytes": bump(wr["rpc_bytes"], hw, drained * dt),
        "lat_sum_s": bump(wr["lat_sum_s"], hw, lat_w),
        "inflight_time": bump(wr["inflight_time"], hw, infl_w * dt),
        "channel_time": bump(wr["channel_time"], hw, live_w * dt),
        "absorbed_bytes": bump(wr["absorbed_bytes"], hw, absorbed * dt),
        "blocked_s": bump(wr["blocked_s"], hw, blocked),
        "active_s": bump(wr["active_s"], hw & act, dt),
    }
    delivered, infl_r, lat_r, rpcs_r, pages_r, live_r = channel_sums(
        r_rate, s["r_pages"])
    out["read"] = {
        "app_bytes": bump(rd["app_bytes"], hr, delivered * dt),
        "app_requests": bump(rd["app_requests"], hr,
                             (delivered * dt) / s["req_g"]),
        "rpc_count": bump(rd["rpc_count"], hr, rpcs_r),
        "rpc_pages": bump(rd["rpc_pages"], hr, pages_r),
        "rpc_bytes": bump(rd["rpc_bytes"], hr, delivered * dt),
        "lat_sum_s": bump(rd["lat_sum_s"], hr, lat_r),
        "inflight_time": bump(rd["inflight_time"], hr, infl_r * dt),
        "channel_time": bump(rd["channel_time"], hr, live_r * dt),
        "absorbed_bytes": cast(rd["absorbed_bytes"]),
        "blocked_s": cast(rd["blocked_s"]),
        "active_s": bump(rd["active_s"], hr, dt),
    }
    dirty_out = np.where(hw, new_dirty, dirty)
    ip = cast(state["inflight_peak"])
    ip = np.where(hw, np.maximum(ip, infl_w), ip)
    ip = np.where(hr, np.maximum(ip, infl_r), ip)
    out.update({
        "dirty": dirty_out,
        "last_drain": np.where(hw, drained, last_drain),
        "dirty_peak": np.maximum(cast(state["dirty_peak"]), dirty_out),
        "inflight_peak": ip,
    })
    return out


def flat(state: Dict) -> Dict[str, np.ndarray]:
    """``{"write.app_bytes": array, ...}``: nested state as flat fields."""
    out = {}
    for k, v in state.items():
        if isinstance(v, dict):
            for kk, vv in v.items():
                out[f"{k}.{kk}"] = np.asarray(vv)
        else:
            out[k] = np.asarray(v)
    return out


def rel_error(before: Dict, ref: Dict, got: Dict) -> tuple:
    """Worst field's error of ``got`` against ``ref`` for one interval.

    Each field's error is its largest absolute gap, taken against the
    larger of the field's largest change over the interval and a
    millionth of its largest value (so a field that did not move is
    judged against its own scale). Returns (error, field)."""
    b, r, g = flat(before), flat(ref), flat(got)
    worst, name = 0.0, ""
    for k, rv in r.items():
        rv = rv.astype(np.float64)
        gv = np.asarray(g[k]).astype(np.float64)
        if gv.shape != rv.shape:
            return float("inf"), k
        scale = max(float(np.max(np.abs(rv - b[k]))) if rv.size else 0.0,
                    1e-6 * float(np.max(np.abs(rv))) if rv.size else 0.0,
                    1e-300)
        gap = float(np.max(np.abs(gv - rv))) if rv.size else 0.0
        if not np.isfinite(gap):
            return float("inf"), k
        err = gap / scale
        if err > worst:
            worst, name = err, k
    return worst, name
