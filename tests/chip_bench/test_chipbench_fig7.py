"""Phased jobs (the paper's Fig 7 sequences, ``mixes/fig7.json``) under
CARAT on the CPU at a small size: jobs of 16 clients switch members
every 2 simulated seconds, so switches land inside a short run. The run
is correct with switched clients, re-probe resets and bootstraps among
the samples, the control fails, and a schedule applied one interval late
or a switched client stepped with its old member turns ``correct``
false. (``BENCHMARK.json`` does not list the cell yet: on the chip its
window compiles the GBDT kernel for new row counts; see PERF.md.)"""
import numpy as np
import pytest

import chipbench_cpu as cpu


def small_fig7():
    cell = cpu.unlisted_cell("frontier_9408.carat_fig7", "frontier_9408",
                             "carat_fig7", like="frontier_9408.carat_striped")
    # one cycle of the schedule warms up, as in the cell
    cell.traffic = dict(cell.traffic, warmup_intervals=16,
                        schedule=dict(cell.traffic["schedule"],
                                      segment_s=2.0, job_clients=16))
    return cell


def test_small_run_is_correct_across_switches():
    keep = {}
    res = cpu.run_small(small_fig7(), keep=keep)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"client_intervals_per_s",
                                   "decision_p95_ms", "setup_s"}
    n = keep["numbers"]
    # the switch interval and the one after it are among the samples:
    # every client switched, and CARAT re-probed and bootstrapped them
    assert n["_switched"] >= cpu.N_CLIENTS
    assert n["_resets"] > 0 and n["_bootstraps"] > 0
    assert n["_decisions_compared"] > 0
    assert res["checks"]["member_mismatch"]["value"] == 0
    sched = keep["ref"].schedule
    switch_samples = [s for s in keep["samples"]
                      if sched.phase(s.t) != sched.phase(s.t - s.dt)]
    assert switch_samples
    for s in keep["samples"]:
        assert np.array_equal(s.members, sched.member_at(s.t))
    ctrl = cpu.control_checks(small_fig7(), keep)
    assert ctrl["fleet_rel_err"]["value"] > 3 * ctrl["fleet_rel_err"]["limit"]
    assert ctrl["gbdt_max_dp"]["value"] > 3 * ctrl["gbdt_max_dp"]["limit"]


def late_schedule(monkeypatch):
    """The workload phase runs with the previous interval's time, so
    every switch lands one interval late."""
    from repro.storage.sim import SchedulePolicy
    step = SchedulePolicy.step

    def patched(self, clients, t, dt):
        return step(self, clients, t - dt, dt)

    monkeypatch.setattr(SchedulePolicy, "step", patched)
    monkeypatch.setattr(SchedulePolicy, "__call__", patched)


def stale_member(monkeypatch):
    """Client 0's workload is switched in name only: the fleet keeps
    stepping it with its old member."""
    from repro.storage.soa import SoACore
    set_workload = SoACore.set_workload

    def patched(self, i, spec_):
        if i == 0 and self.specs[0] is not None:
            set_workload(self, i, self.specs[0])
            self.specs[0] = spec_
        else:
            set_workload(self, i, spec_)

    monkeypatch.setattr(SoACore, "set_workload", patched)


@pytest.mark.parametrize("fault", [late_schedule, stale_member],
                         ids=["late_schedule", "stale_member"])
def test_fault_in_the_switch_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    res = cpu.run_small(small_fig7())
    assert res["correct"] is False
    c = res["checks"]["fleet_rel_err"]
    assert c["value"] > c["limit"]
