"""What decides ``correct``: samples of what the timed path produced,
compared with the plain references once the window has closed.

At intervals drawn from the seed the sampler keeps a copy of the fleet
state (on the device) before and after the interval, the OST noise
stream's position, and the client configurations. In CARAT cells it also
keeps the fleet state and configurations one interval earlier (the
counters the probe differences) and each controller's state before the
probe (stage machine and stage factors), and records what the decision
step did: which clients it decided and for which operation, the feature
rows and probabilities of each scorer call, and the proposals. Nothing is
compared inside the window.

The numbers compared, each with a limit of its own (``limits/<cell>.json``):

* ``fleet_rel_err``: the worst field's gap between the fleet state the
  fused step produced and the float64 reference's (``fleet_ref``);
* ``noise_draws``: OST noise streams whose position after the interval
  differs from the reference's (each interval draws one factor per
  active OST);
* ``due_mismatch``: clients that the program decided and the reference
  did not, or the reverse, or for the other operation (the reference
  works out from the sampled counters and controller state which
  clients are due at the probe);
* ``features_max_err``: the largest gap between a feature row that
  reached the scorer and the reference's row for that client;
* ``gbdt_max_dp``: the largest gap between a kernel probability and the
  reference GBDT's on the same rows, over every client and candidate;
* ``alg1_mismatch``: proposals that differ from Algorithm 1 applied to
  the kernel's probabilities;
* ``applied_mismatch``: clients whose RPC configuration after the
  interval is not the one the reference expects: Algorithm 1's choice
  for a decided client, the default after a re-probe, the best
  candidate of a bootstrap pick, else the one before;
* ``stage2_mismatch``: clients whose cache limit after the interval is
  not Algorithm 2's for a node at its stage-2 boundary (over all of the
  node's members, with the budget its arbiter has), or the one before
  for every other node;
* ``member_mismatch`` (mixes with phased jobs): clients whose workload
  in the sampled interval, as the program holds it once its workload
  phase has run, is not the member the traffic's schedule gives.

The references are the modules the cell's configuration names
(``fleet_ref`` and ``tuner_ref`` unless it names others). With phased
jobs the fleet reference steps each client with the member the schedule
gives at the sampled instant, and the sampler adds to the samples drawn
from the seed the interval of the first switch it can reach in the
window and the interval after it, where CARAT re-probes and bootstraps
the switched clients.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

from chipbench import fleet_ref, tuner_ref

# draws of the sample times come from a stream of their own
_SAMPLE_STREAM = 0x5A3D


def sample_times(seed: int, seconds: float, k: int) -> List[float]:
    """``k`` sorted window offsets (seconds) at which to sample."""
    rng = np.random.Generator(np.random.PCG64(
        [int(seed) & ((1 << 64) - 1), _SAMPLE_STREAM]))
    return sorted(float(x) for x in rng.uniform(0.05, 0.9, size=k) * seconds)


@dataclass
class Sample:
    t: float
    dt: float
    before: object = None          # device copy, then host dict
    after: object = None
    rng_before: dict = None
    rng_after: dict = None
    cfg_before: tuple = None       # (window, inflight, cache_mb) arrays
    cfg_after: tuple = None
    prev: object = None            # CARAT: state one interval earlier
    cfg_prev: tuple = None
    ctl: dict = None               # CARAT: controller state before
    pending: list = field(default_factory=list)   # (client_id, op)
    proposals: list = field(default_factory=list)
    scored: list = field(default_factory=list)    # (op, H, probs)
    members: np.ndarray = None     # phased jobs: the program's members


class _ScorerSpy:
    """Stands in for one op's grid scorer; records while armed."""

    def __init__(self, inner, sampler: "Sampler", op: str):
        self._inner = inner
        self._sampler = sampler
        self._op = op

    def __call__(self, H, *a, **kw):
        out = self._inner(H, *a, **kw)
        cur = self._sampler.current
        if cur is not None:
            cur.scored.append((self._op, np.array(H, dtype=np.float32),
                               np.array(out, dtype=np.float64)))
        return out

    def __getattr__(self, name):
        return getattr(self._inner, name)


def controller_state(policy) -> Dict[str, np.ndarray]:
    """Each CARAT controller's state before a probe, in client order: has
    it a previous sample and how deep is its history, its stage machine
    (inactive time, the long-inactive flag, the last active signature,
    the re-probe and bootstrap flags) and its stage factors."""
    cs = policy.controllers
    n = len(cs)

    def col(get, dtype=np.float64):
        return np.fromiter((get(c) for c in cs), dtype, n)

    def sig(get):
        return col(lambda c: np.nan if c._last_sig is None
                   or get(c._last_sig) is None else get(c._last_sig))

    return {
        "client_id": col(lambda c: c.client_id, np.int64),
        "has_prev": col(lambda c: c.builder._prev is not None, bool),
        "n_hist": col(lambda c: len(c.builder.history), np.int64),
        "inactive_s": col(lambda c: c.inactive_s),
        "was_inactive_long": col(lambda c: c.was_inactive_long, bool),
        "has_sig": col(lambda c: c._last_sig is not None, bool),
        "sig_read_share": sig(lambda g: g.read_share),
        "sig_req_read": sig(lambda g: g.req_read),
        "sig_req_write": sig(lambda g: g.req_write),
        "reprobe_pending": col(lambda c: c._reprobe_pending, bool),
        "last_reprobe_t": col(lambda c: c._last_reprobe_t),
        "bootstrap_pending": col(lambda c: c._bootstrap_pending, bool),
        "sf_saw": col(lambda c: c.stage_factors.saw_activity, bool),
        "sf_peak_cache": col(lambda c: c.stage_factors.peak_cache_bytes),
        "sf_peak_inflight": col(
            lambda c: c.stage_factors.peak_inflight_bytes),
        "sf_write_rpcs": col(lambda c: c.stage_factors.write_rpcs),
    }


class Sampler:
    """Keeps the samples of one run (see the module docstring).

    The harness calls :meth:`before` ahead of each interval, outside its
    timing, and :meth:`after` once the interval has returned. In CARAT
    cells a sample spans two intervals: the first keeps the state and
    configurations the probe of the second differences against."""

    def __init__(self, sim, policy, times: List[float], schedule=None,
                 members: Optional[List[str]] = None):
        self.sim = sim
        self.policy = policy
        self.times = list(times)
        self.samples: List[Sample] = []
        self.current: Optional[Sample] = None
        self._armed: Optional[tuple] = None
        # phased jobs: the switch interval to sample and the one after
        self.schedule = schedule
        self._pair: Optional[List[float]] = None
        self._member_of = {m: i for i, m in enumerate(members or [])}
        if policy is not None:
            ids = [c.client_id for c in policy.controllers]
            if ids != list(range(len(sim.clients))):
                raise RuntimeError("the CARAT controllers are not one per "
                                   "client in client order")
            self._spy_policy(policy)

    def _spy_policy(self, policy) -> None:
        decide = policy.decide_many
        sampler = self

        def decide_many(obs_batch):
            out = decide(obs_batch)
            cur = sampler.current
            if cur is not None:
                cur.pending = [(c.client_id, op) for c, op, _ in obs_batch]
                cur.proposals = [p for p, _ in out]
            return out

        policy.decide_many = decide_many
        grid = policy.tuner.grid_models
        for op in list(grid):
            grid[op] = _ScorerSpy(grid[op], self, op)

    @property
    def open(self) -> bool:
        """A sample is still to be taken."""
        return (bool(self.times) or self._armed is not None
                or bool(self._pair))

    def _at(self, t: float, when: float) -> bool:
        return abs(t - when) < 0.5 * self.sim.interval_s

    def before(self, elapsed: float) -> bool:
        """Take the copies due before the next interval; True when that
        interval is a sampled one."""
        sim = self.sim
        t, dt = sim.t, sim.interval_s
        # a CARAT sample needs the interval before it, the arm
        lead = dt if self.policy is not None else 0.0
        if self.schedule is not None and self._pair is None:
            first = self.schedule.next_switch(t + lead)
            self._pair = [first, first + dt]
        pair = self._pair
        if self._armed is None:
            if pair and self._at(t, pair[0]):
                pass                    # the switch interval (no arm)
            elif pair and lead and self._at(t + lead, pair[0]):
                self._armed = (self._copy_state(), self._cfg())
                return False
            elif not (self.times and elapsed >= self.times[0]):
                return False
            elif pair and t + 2 * lead >= pair[0] - 0.5 * dt:
                return False            # would overlap the switch pair
            else:
                self.times.pop(0)
                if self.policy is not None:
                    self._armed = (self._copy_state(), self._cfg())
                    return False
        if pair and self._at(t, pair[0]):
            pair.pop(0)
        cur = Sample(t=sim.t, dt=sim.interval_s,
                     before=self._copy_state(),
                     rng_before=copy.deepcopy(
                         sim.cluster.rng.gen.bit_generator.state),
                     cfg_before=self._cfg())
        if self._armed is not None:
            cur.prev, cur.cfg_prev = self._armed
            cur.ctl = controller_state(self.policy)
            self._armed = None
        self.current = cur
        return True

    def after(self) -> None:
        cur = self.current
        if cur is None:
            return
        cur.after = self._copy_state()
        cur.rng_after = copy.deepcopy(
            self.sim.cluster.rng.gen.bit_generator.state)
        cur.cfg_after = self._cfg()
        if self.schedule is not None:
            # each client's workload once the workload phase has run
            specs = self.sim.core.specs
            cur.members = np.fromiter(
                (self._member_of.get(w.name, -1) for w in specs), np.int64,
                len(specs))
        self.samples.append(cur)
        self.current = None
        if (self.policy is not None and self._pair
                and self._at(cur.t + cur.dt, self._pair[0])):
            # the next interval is sampled too: this one is its arm
            self._armed = (cur.before, cur.cfg_before)

    def _cfg(self):
        core = self.sim.core
        return (core.cfg_window.copy(), core.cfg_inflight.copy(),
                core.cfg_cache_mb.copy())

    def _copy_state(self):
        import jax
        import jax.numpy as jnp
        fleet = self.sim.device_fleet
        if fleet.device_stale or fleet._state is None:
            raise RuntimeError("the device fleet state is not on the "
                               "device at a sampled interval")
        return jax.tree.map(lambda a: jnp.array(a, copy=True), fleet._state)

    def warm_up(self) -> None:
        """Compile the state copies the samples take (set-up)."""
        import jax
        jax.block_until_ready(self._copy_state())
        if self.policy is not None:
            controller_state(self.policy)

    def pull(self) -> None:
        """Bring every sampled state to the host (after the window)."""
        import jax
        for s in self.samples:
            s.before = jax.device_get(s.before)
            s.after = jax.device_get(s.after)
            s.prev = jax.device_get(s.prev)


# ----------------------------------------------------------- comparison
@dataclass
class Reference:
    """What the references need beside the samples: the deployment's
    constants, each client's workload and stripe, and the policy's."""
    pfs: Dict
    members: List[dict]
    member_idx: np.ndarray
    offsets: np.ndarray
    policy: Dict
    clients_per_node: int = 1
    models: Dict[str, dict] = field(default_factory=dict)
    schedule: object = None        # traffic.Schedule of phased jobs
    fleet: ModuleType = fleet_ref  # the references the cell names
    tuner: ModuleType = tuner_ref


def fleet_numbers(ref: Reference, samples: List[Sample],
                  dtype=np.float64, use_program: bool = True) -> Dict:
    """``fleet_rel_err`` and ``noise_draws`` over the samples (and, with
    phased jobs, ``member_mismatch``). With ``use_program=False`` the
    reference at ``dtype`` stands in for the program (the control)."""
    fleet_ref = ref.fleet
    wl = fleet_ref.member_arrays(ref.members, ref.member_idx)
    worst, where, draws = 0.0, "", 0
    members, switched = 0, 0
    for s in samples:
        if ref.schedule is not None:
            idx = ref.schedule.member_at(s.t)
            wl = fleet_ref.member_arrays(ref.members, idx)
            switched += int(np.count_nonzero(
                idx != ref.schedule.member_at(s.t - s.dt)))
            if use_program:
                members += int(np.count_nonzero(s.members != idx))
        st64 = fleet_ref.statics(ref.pfs, wl, *s.cfg_before, ref.offsets)
        act = fleet_ref.duty_active(st64, s.t)
        mask = fleet_ref.ost_active(st64, np.asarray(s.before["dirty"]),
                                    act, int(ref.pfs["n_osts"]))
        noise, rng_after = fleet_ref.noise_from(s.rng_before, mask,
                                                ref.pfs["noise_sigma"])
        want = fleet_ref.step(ref.pfs, st64, s.before, s.t, s.dt, noise)
        if use_program:
            got = s.after
            draws += int(rng_after != s.rng_after)
        else:
            st = fleet_ref.statics(ref.pfs, wl, *s.cfg_before, ref.offsets,
                                   dtype=dtype)
            got = fleet_ref.step(ref.pfs, st, s.before, s.t, s.dt, noise)
        err, name = fleet_ref.rel_error(s.before, want, got)
        if err > worst or not where:
            worst, where = err, name
    out = {"fleet_rel_err": worst, "noise_draws": draws,
           "_fleet_worst_field": where}
    if ref.schedule is not None:
        out.update(member_mismatch=members, _switched=switched)
    return out


def tuner_numbers(ref: Reference, samples: List[Sample],
                  use_program: bool = True) -> Dict:
    """The CARAT numbers over the samples (see the module docstring).
    With ``use_program=False`` the reference one precision lower stands
    in for the program (the control): its feature rows rounded to
    bfloat16 and its probabilities from the bfloat16 GBDT."""
    tuner_ref = ref.tuner
    pol = ref.policy
    per_node = int(ref.clients_per_node)
    th = tuner_ref.theta(pol["rpc_window_pages"], pol["rpcs_in_flight"])
    cands = [(w, f) for w in pol["rpc_window_pages"]
             for f in pol["rpcs_in_flight"]]
    grid = pol["dirty_cache_mb"]
    # a node's stage-2 budget, as NodeCacheArbiter.budget() gives it:
    # node_budget_share x the largest cache limit x the node's members
    share = pol["node_budget_share"] * grid[-1]
    default = (pol["defaults"]["default_rpc_window"],
               pol["defaults"]["default_in_flight"])
    dp, feat_err = 0.0, 0.0
    due, alg1, applied, stage2 = 0, 0, 0, 0
    scored, decided, nodes, boots, resets = 0, 0, 0, 0, 0
    for s in samples:
        obs = tuner_ref.observe(pol, (s.prev, s.before, s.after),
                                (s.cfg_prev, s.cfg_before), s.ctl,
                                s.t + s.dt, s.dt)
        want_ids = np.nonzero(obs["pending"])[0]
        want = {(int(i), tuner_ref.OPS[obs["op"][i]]) for i in want_ids}
        got = {(int(c), op) for c, op in s.pending} if use_program \
            else want
        due += len(got ^ want)
        prop_of = dict(zip((int(c) for c, _ in s.pending), s.proposals))
        w0, f0, c0 = (np.array(a) for a in s.cfg_before)
        ew, ef, ec = w0.copy(), f0.copy(), c0.copy()
        ew[obs["reset"]], ef[obs["reset"]] = default
        resets += int(obs["reset"].sum())
        for code, op in enumerate(tuner_ref.OPS):
            ids = want_ids[obs["op"][want_ids] == code]
            F = obs["feats"][ids]
            if use_program:
                calls = [(H, P) for o, H, P in s.scored if o == op]
                if not calls and not ids.size:
                    continue
                if len(calls) != 1 or calls[0][0].shape != F.shape:
                    feat_err = dp = float("inf")
                    continue
                H, P = calls[0]
            else:
                H = tuner_ref.bf16(F)
                P = tuner_ref.proba(ref.models[op], H, th, lower=True)
            if F.size:
                feat_err = max(feat_err, float(np.max(np.abs(
                    H.astype(np.float64) - F.astype(np.float64)))))
            want_p = tuner_ref.proba(ref.models[op], H, th)
            if P.shape != want_p.shape:
                dp = float("inf")
                continue
            if P.size:
                dp = max(dp, float(np.max(np.abs(P - want_p))))
            scored += P.size
            for cid, row in zip(ids, P):
                k = tuner_ref.algorithm1(op, row, th, pol["prob_tau"],
                                         pol["alpha"], pol["beta"])
                chosen = tuple(cands[k]) if k >= 0 else None
                if use_program:
                    prop = prop_of.get(int(cid), "missing")
                    mine = prop if prop in (None, "missing") \
                        else tuple(int(v) for v in prop)
                else:
                    mine = chosen
                alg1 += int(mine != chosen)
                decided += 1
                if chosen is not None:
                    ew[cid], ef[cid] = chosen
        boot = np.nonzero(obs["bootstrap"])[0]
        boots += boot.size
        # node j holds clients jk ... jk+k-1; a boundary of any member
        # re-allocates the whole node
        for j in np.unique(np.nonzero(obs["boundary"])[0] // per_node):
            m = slice(j * per_node, (j + 1) * per_node)
            ec[m] = tuner_ref.algorithm2(
                obs["saw"][m], obs["peak_cache"][m], obs["peak_inflight"][m],
                obs["write_rpcs"][m], share * len(ec[m]), grid)
            nodes += 1
        if not use_program:
            continue
        w1, f1, c1 = (np.array(a) for a in s.cfg_after)
        off = (w1 != ew) | (f1 != ef)
        off[boot] = False
        applied += int(off.sum())
        for i in boot:
            # the bootstrap takes the most probable candidate; a tie is
            # broken either way
            op = tuner_ref.OPS[obs["op"][i]]
            p = tuner_ref.proba(ref.models[op], obs["feats"][i:i + 1],
                                th)[0]
            k = cands.index((int(w1[i]), int(f1[i]))) \
                if (int(w1[i]), int(f1[i])) in cands else None
            applied += int(k is None or p[k] < p.max() - 1e-9)
        stage2 += int((c1 != ec).sum())
    return {"due_mismatch": due, "features_max_err": feat_err,
            "gbdt_max_dp": dp, "alg1_mismatch": alg1,
            "applied_mismatch": applied, "stage2_mismatch": stage2,
            "_probs_compared": scored, "_decisions_compared": decided,
            "_stage2_nodes_compared": nodes, "_bootstraps": boots,
            "_resets": resets}


def judge(numbers: Dict, limits: Dict) -> Dict[str, dict]:
    """``{name: {"value": v, "limit": l, "ok": bool}}`` for each limit."""
    out = {}
    for name, lim in limits["numbers"].items():
        v = numbers.get(name)
        ok = v is not None and np.isfinite(v) and v <= lim
        out[name] = {"value": v, "limit": lim, "ok": bool(ok)}
    return out
