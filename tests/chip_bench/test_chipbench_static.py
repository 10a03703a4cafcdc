"""The static cells' run on the CPU at a small size: correct as it
stands, the lower-precision control fails, and each fault planted in the
fused fleet step turns ``correct`` false."""
import pytest

import chipbench_cpu as cpu

CELL = "frontier_9408.static_striped"


def test_small_run_is_correct_and_reports_its_metrics():
    keep = {}
    res = cpu.run_small(CELL, keep=keep)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"client_intervals_per_s",
                                   "interval_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert len(keep["samples"]) == 2


def test_control_fails_the_fleet_limit():
    keep = {}
    cpu.run_small(CELL, keep=keep)
    ctrl = cpu.control_checks(CELL, keep)
    assert ctrl["fleet_rel_err"]["ok"] is False
    assert ctrl["fleet_rel_err"]["value"] > \
        3 * ctrl["fleet_rel_err"]["limit"]


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_fault_in_the_fleet_step_is_caught(monkeypatch, kind):
    cpu.break_fleet_step(monkeypatch, kind)
    res = cpu.run_small(CELL)
    assert res["correct"] is False
    assert res["checks"]["fleet_rel_err"]["value"] > \
        res["checks"]["fleet_rel_err"]["limit"]
