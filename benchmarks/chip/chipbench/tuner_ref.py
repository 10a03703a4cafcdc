"""Plain reference of CARAT's decision step (paper §III-A to §III-E).

* :func:`observe` — one probe of every client's controller: the Table II
  metrics from differenced counters, the feature rows, the stage
  machine (I/O-inactive stages, the stage-2 boundary, the phase re-probe
  and its bootstrap pick), and so which clients are due for a stage-1
  decision, with what features.
* :func:`proba` — oblivious-GBDT probabilities of a client x candidate
  cross product, straight from the tree tables (one comparison per
  level, the leaf index bit-packed with level 0 as its high bit, leaf
  values summed in float64), with a ``lower`` mode for the control:
  features, thresholds and leaves rounded to bfloat16 and the sum taken
  in float32, the step below the kernel's float32.
* :func:`algorithm1` — the conditional-score greedy selection over one
  client's candidate probabilities.
* :func:`algorithm2` — one node's stage-2 cache allocation.

Nothing here imports the program; the models are the benchmark's own
tables under ``models/``.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

from chipbench.fleet_ref import PAGE_SIZE

MB = 1024.0 * 1024.0
OPS = ("read", "write")
_COUNTERS = ("app_bytes", "app_requests", "rpc_count", "rpc_pages",
             "rpc_bytes", "lat_sum_s", "inflight_time", "channel_time")


def load_model(path: str) -> Dict[str, np.ndarray]:
    z = np.load(path)
    return {"feat": z["feat"].astype(np.int64), "thr": z["thr"]
            .astype(np.float32), "leaf": z["leaf"].astype(np.float32),
            "base": float(z["base"][0]), "n_features": int(z["n_features"][0])}


def theta(windows: Sequence[int], inflights: Sequence[int]) -> np.ndarray:
    """(n_candidates, 2) log2 features of every (window, in-flight) pair,
    window-major."""
    return np.array([[math.log2(w), math.log2(f)] for w in windows
                     for f in inflights], dtype=np.float32)


def bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even)."""
    b = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    rounded = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return np.where(np.isfinite(x), rounded.view(np.float32),
                    x.astype(np.float32))


def proba(model: Dict, H: np.ndarray, th: np.ndarray,
          lower: bool = False, block: int = 4096) -> np.ndarray:
    """(n, c) probabilities of every client row of ``H`` with every
    candidate row of ``th`` (features ``[H | theta]``)."""
    H = np.asarray(H, dtype=np.float32)
    feat, thr, leaf = model["feat"], model["thr"], model["leaf"]
    if lower:
        H, th, thr, leaf = bf16(H), bf16(th), bf16(thr), bf16(leaf)
    n, c = H.shape[0], th.shape[0]
    n_trees, depth = feat.shape
    out = np.empty((n, c), dtype=np.float64)
    rows = max(1, block // c)
    tree_off = np.arange(n_trees)[None, :] * leaf.shape[1]
    flat_leaf = leaf.ravel()
    for i0 in range(0, n, rows):
        h = H[i0:i0 + rows]
        X = np.concatenate([np.repeat(h, c, axis=0),
                            np.tile(th, (h.shape[0], 1))], axis=1)
        idx = np.zeros((X.shape[0], n_trees), dtype=np.int64)
        for level in range(depth):
            bit = X[:, feat[:, level]] > thr[None, :, level]
            idx = idx * 2 + bit
        vals = flat_leaf[idx + tree_off]
        if lower:
            logit = np.float32(model["base"]) + vals.sum(
                axis=1, dtype=np.float32)
        else:
            logit = model["base"] + vals.astype(np.float64).sum(axis=1)
        logit = np.clip(logit.astype(np.float64), -30.0, 30.0)
        out[i0:i0 + h.shape[0]] = (1.0 / (1.0 + np.exp(-logit))).reshape(
            h.shape[0], c)
    return out


def _metrics(cur: Dict, prev: Dict, cfg: tuple, op: str,
             interval_s: float) -> np.ndarray:
    """(n, 6) Table II metrics of one op direction over one probe: page
    and channel use, latency per page, volume per channel, dirty-cache
    use and the estimated in-place update (writes only)."""
    d = {k: np.asarray(cur[op][k], np.float64)
         - np.asarray(prev[op][k], np.float64) for k in _COUNTERS}
    window = np.maximum(np.asarray(cfg[0]), 1).astype(np.float64)
    cap = np.maximum(np.asarray(cfg[1]), 1).astype(np.float64)
    cache_bytes = np.maximum(np.asarray(cfg[2]), 1) * 1024.0 * 1024.0
    rpcs, pages = d["rpc_count"], d["rpc_pages"]
    n_chan = np.maximum(d["channel_time"] / interval_s, 1.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        page_util = np.where(rpcs > 0, pages / rpcs / window, 0.0)
        unit_lat = np.where(pages > 0, d["lat_sum_s"] / pages, 0.0)
    chan_util = d["inflight_time"] / interval_s / cap / n_chan
    volume = d["rpc_bytes"] / n_chan
    zero = np.zeros_like(volume)
    if op == "write":
        dirty = np.asarray(cur["dirty"], np.float64)
        dirty_util = dirty / cache_bytes
        est = np.maximum(0.0, d["app_bytes"] - d["rpc_bytes"]
                         - (dirty - np.asarray(prev["dirty"], np.float64)))
    else:
        dirty_util, est = zero, zero
    return np.stack([np.clip(page_util, 0.0, 1.5),
                     np.clip(chan_util, 0.0, 1.5), unit_lat, volume,
                     np.clip(dirty_util, 0.0, 1.2), est], axis=1)


def _normalize(raw: np.ndarray) -> np.ndarray:
    """Utilizations as they are, latency log-scaled around 1 us to 1 ms,
    volumes as log-bytes; in float32."""
    out = raw.astype(np.float32)
    for base in range(0, out.shape[1], 6):
        out[:, base + 2] = np.log10(np.maximum(out[:, base + 2],
                                               np.float32(1e-7))) + 7.0
        for j in (3, 5):
            out[:, base + j] = np.log10(np.maximum(out[:, base + j],
                                                   np.float32(1.0))) / 10.0
    return out


def observe(pol: Dict, states: Sequence[Dict], cfgs: Sequence[tuple],
            ctl: Dict[str, np.ndarray], t: float, dt: float) -> Dict:
    """One probe at time ``t`` of every client's CARAT controller.

    ``states`` are the fleet states after the two previous intervals and
    after this one (the counters the probe differences), ``cfgs`` the
    client configurations in effect during the previous interval's probe
    and this one's, and ``ctl`` each controller's state before the probe
    (its stage machine and stage factors). Returns, per client: whether
    it is ``pending`` (a stage-1 decision is due), its ``op`` (0 read, 1
    write) and ``feats``, whether its RPC configuration is ``reset`` to
    the default (re-probe) or set by a ``bootstrap`` pick, whether it
    crosses a stage-2 ``boundary``, and its stage factors after the
    probe (``saw``, ``peak_cache``, ``peak_inflight``, ``write_rpcs``).
    """
    if int(pol["history_k"]) != 1:
        raise ValueError("the reference keeps a history of one probe")
    iv = float(pol["probe_interval_s"])
    s2, s1, s0 = states
    c1, c0 = cfgs
    now = {op: _metrics(s0, s1, c0, op, iv) for op in OPS}
    before = {op: _metrics(s1, s2, c1, op, iv) for op in OPS}
    snap = ctl["has_prev"].astype(bool)

    def delta(op, key):
        return (np.asarray(s0[op][key], np.float64)
                - np.asarray(s1[op][key], np.float64))

    rd_req, wr_req = delta("read", "app_requests"), delta("write",
                                                          "app_requests")
    rd_b, wr_b = delta("read", "app_bytes"), delta("write", "app_bytes")
    active = snap & ((rd_req > 0) | (wr_req > 0))

    # stage factors, updated by every probe that has a snapshot
    wr_now = now["write"]
    saw = ctl["sf_saw"].astype(bool) | active
    cache_bytes = np.asarray(c0[2], np.float64) * MB
    peak_cache = np.where(snap, np.maximum(
        ctl["sf_peak_cache"], wr_now[:, 4] * cache_bytes),
        ctl["sf_peak_cache"])
    infl_bytes = (np.asarray(s0["inflight_peak"], np.float64)
                  * np.asarray(c0[0], np.float64) * PAGE_SIZE)
    peak_inflight = np.where(snap, np.maximum(ctl["sf_peak_inflight"],
                                              infl_bytes),
                             ctl["sf_peak_inflight"])
    write_rpcs = np.where(snap, ctl["sf_write_rpcs"] + wr_now[:, 3],
                          ctl["sf_write_rpcs"])

    # the inactive -> active boundary (stage 2)
    boundary = active & ctl["was_inactive_long"].astype(bool)

    # phase re-probe: the app signature against the last active one
    total = rd_b + wr_b
    with np.errstate(divide="ignore", invalid="ignore"):
        share = np.where(total > 0, rd_b / total, 0.0)
        req_rd = np.where(rd_req > 0.5, rd_b / rd_req, np.nan)
        req_wr = np.where(wr_req > 0.5, wr_b / wr_req, np.nan)
    prev_share = ctl["sig_read_share"]
    changed = (((prev_share >= 0.7) & (share <= 0.3))
               | ((prev_share <= 0.3) & (share >= 0.7)))
    ratio = float(pol["reprobe_req_ratio"])
    for a, b in ((ctl["sig_req_read"], req_rd), (ctl["sig_req_write"],
                                                 req_wr)):
        both = ~np.isnan(a) & ~np.isnan(b)
        lo, hi = np.fmin(a, b), np.fmax(a, b)
        changed |= both & (hi > lo * ratio)
    changed &= ctl["has_sig"].astype(bool)
    reset = np.zeros_like(active)
    boot = ctl["bootstrap_pending"].astype(bool)
    if pol["reprobe_on_change"]:
        fire = active & (ctl["reprobe_pending"].astype(bool) | changed) & (
            t - ctl["last_reprobe_t"] >= float(pol["reprobe_cooldown_s"]))
        d = pol["defaults"]
        at_default = ((np.asarray(c0[0]) == d["default_rpc_window"])
                      & (np.asarray(c0[1]) == d["default_in_flight"]))
        reset = fire & ~at_default
        boot = boot | fire

    # stage 1: features from this probe's and the last probe's metrics
    op = np.where(now["read"][:, 3] >= now["write"][:, 3], 0, 1)
    pick = op[:, None] == 0
    raw = np.concatenate([np.where(pick, now["read"], now["write"]),
                          np.where(pick, before["read"], before["write"])],
                         axis=1)
    f = _normalize(raw)
    cfg = np.stack([np.log2(np.maximum(np.asarray(c0[0]), 1)
                            .astype(np.float64)),
                    np.log2(np.maximum(np.asarray(c0[1]), 1)
                            .astype(np.float64))], axis=1).astype(np.float32)
    feats = np.concatenate([f, f[:, :6] - f[:, 6:12], cfg], axis=1)
    has_feats = active & ~reset & (ctl["n_hist"] >= 1)
    bootstrap = has_feats & boot
    return {"pending": has_feats & ~boot, "op": op, "feats": feats,
            "reset": reset, "bootstrap": bootstrap, "boundary": boundary,
            "saw": saw, "peak_cache": peak_cache,
            "peak_inflight": peak_inflight, "write_rpcs": write_rpcs}


def algorithm1(op: str, probs: np.ndarray, th: np.ndarray, tau: float,
               alpha: float, beta: float) -> int:
    """Index of the chosen candidate, or -1 to keep the current config:
    keep candidates above ``tau``, MinMax-normalize their parameters
    over the kept set, rank by the write or read score."""
    keep = np.nonzero(probs > tau)[0]
    if keep.size == 0:
        return -1
    t = th[keep]
    lo, hi = t.min(axis=0), t.max(axis=0)
    tn = (t - lo) / np.maximum(hi - lo, 1e-9)
    f = probs[keep]
    if op == "write":
        score = f * (1.0 + beta * tn.sum(axis=1))
    else:
        score = f * (1.0 + alpha * tn[:, 0]) + tn[:, 1]
    return int(keep[int(np.argmax(score))])


def algorithm2(active: Sequence[bool], peak_cache: Sequence[float],
               peak_inflight: Sequence[float], write_rpcs: Sequence[float],
               budget_mb: float, grid: Sequence[int]) -> List[int]:
    """Dirty-cache limits (MB) of one node's clients, in member order."""
    lo, hi = grid[0], grid[-1]
    out: List[Optional[int]] = [None] * len(active)
    n_idle = sum(1 for a in active if not a)
    n_act = len(active) - n_idle
    remaining = max(budget_mb - lo * n_idle, 0.0)
    total = sum(max(w, 0.0) for w, a in zip(write_rpcs, active) if a) or 1.0
    for i, a in enumerate(active):
        if not a:
            out[i] = lo
        elif remaining <= 0.0:
            out[i] = lo
        elif hi * n_act <= remaining:
            out[i] = hi
        else:
            want = max(peak_cache[i] / MB, peak_inflight[i] / MB,
                       (write_rpcs[i] / total) * remaining)
            out[i] = next((v for v in grid if v >= want), hi)
    return [int(v) for v in out]
