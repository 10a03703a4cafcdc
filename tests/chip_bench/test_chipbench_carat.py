"""The CARAT cell's run on the CPU at a small size: correct as it stands,
its traced run reports the per-layer metrics the CPU can read, the
bfloat16 control put in the program's place fails the feature and kernel
limits, and faults planted in the due clients, the features, the scores
or the decisions turn ``correct`` false."""
import numpy as np

import chipbench_cpu as cpu

CELL = "frontier_9408.carat_striped"


def test_small_run_is_correct():
    keep = {}
    res = cpu.run_small(CELL, keep=keep)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"client_intervals_per_s",
                                   "decision_p95_ms", "setup_s"}
    assert keep["numbers"]["_probs_compared"] > 0
    assert keep["numbers"]["_decisions_compared"] > 0
    assert {"due_mismatch", "features_max_err"} <= set(res["checks"])
    ctrl = cpu.control_checks(CELL, keep)
    assert not all(c["ok"] for c in ctrl.values())
    for name in ("gbdt_max_dp", "features_max_err"):
        assert ctrl[name]["value"] > 3 * ctrl[name]["limit"], ctrl[name]


def test_traced_run_reads_the_per_layer_metrics():
    res = cpu.run_small(CELL, trace=True)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    assert m["policy_observe_ms"]["value"] > 0
    assert m["policy_decide_ms"]["value"] > 0
    assert m["compiles_in_window"]["value"] == 0
    assert "client_intervals_per_s" not in m
    assert "busy_s" in res["device"] and "window_s" in res["device"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_altered_score_is_caught(monkeypatch):
    cpu.break_scores(monkeypatch)
    res = cpu.run_small(CELL)
    assert res["correct"] is False
    c = res["checks"]["gbdt_max_dp"]
    assert c["value"] > c["limit"]


def test_altered_decision_is_caught(monkeypatch):
    cpu.break_decisions(monkeypatch)
    res = cpu.run_small(CELL)
    assert res["correct"] is False
    assert res["checks"]["alg1_mismatch"]["value"] > 0


def test_fleet_step_left_unchanged_is_caught(monkeypatch):
    cpu.break_fleet_step(monkeypatch, "unchanged")
    res = cpu.run_small(CELL)
    assert res["correct"] is False
    assert np.isfinite(res["checks"]["fleet_rel_err"]["value"])


def test_half_of_the_due_clients_left_out_is_caught(monkeypatch):
    cpu.skip_half_of_the_due_clients(monkeypatch)
    res = cpu.run_small(CELL)
    assert res["correct"] is False
    assert res["checks"]["due_mismatch"]["value"] > 0


def test_altered_features_are_caught(monkeypatch):
    cpu.alter_features(monkeypatch)
    res = cpu.run_small(CELL)
    assert res["correct"] is False
    c = res["checks"]["features_max_err"]
    assert c["value"] > c["limit"]


class _SwitchWorkloads:
    """Swaps a rotating eighth of the clients between a read-only and a
    write-only member before each interval, so their signatures flip and
    CARAT re-probes and then bootstraps them."""
    phase = "workload"

    def __init__(self, pair):
        self.pair = pair
        self.k = 0

    def __call__(self, clients, t, dt):
        self.k += 1
        a, b = self.pair
        for i, c in enumerate(clients):
            if (i + self.k) % 8 == 0 and c.workload.name in (a.name,
                                                               b.name):
                c.set_workload(b if c.workload.name == a.name else a)


def test_reference_follows_reprobes_and_bootstraps(monkeypatch):
    from repro.storage.workloads import WorkloadSpec
    from chipbench import harness
    policy = harness._policy

    def with_switches(cell, sim):
        members = [WorkloadSpec(**m) for m in cell.traffic["members"]]
        sim.attach_policy(_SwitchWorkloads((members[0], members[1])))
        return policy(cell, sim)

    monkeypatch.setattr(harness, "_policy", with_switches)
    keep = {}
    cpu.run_small(CELL, keep=keep)
    n = keep["numbers"]
    assert n["_resets"] > 0 and n["_bootstraps"] > 0
    assert n["_decisions_compared"] > 0
    for name in ("due_mismatch", "alg1_mismatch", "applied_mismatch",
                 "stage2_mismatch"):
        assert n[name] == 0, (name, n)
    assert n["features_max_err"] <= 1e-6
