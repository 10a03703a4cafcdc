"""Shared client nodes on the CPU at a small size: a copy of the CARAT
cell with four clients to a node is correct with stage-2 nodes of four
members compared, and a stage-2 allocation altered in one member turns
``correct`` false.

The samples are taken at every other interval from the window's start
(3.5 s into the run), so the intervals that start at 4.0 and 6.0 s, where
the DLIO members (bursts of 2 s and 4 s) come back from more than a
second of inactivity, are among them."""
import chipbench_cpu as cpu
from chipbench import check, spec

CELL = "frontier_9408.carat_striped"


def four_to_a_node(monkeypatch):
    cell = spec.load_cell(CELL)
    cell.config = dict(cell.config, clients_per_node=4)
    cell.traffic = dict(cell.traffic, warmup_intervals=7)
    monkeypatch.setattr(check, "sample_times",
                        lambda seed, seconds, k: [0.0] * 4)
    return cell


def test_shared_nodes_are_correct(monkeypatch):
    keep = {}
    res = cpu.run_small(four_to_a_node(monkeypatch), keep=keep)
    assert res["correct"] is True, res["checks"]
    assert keep["numbers"]["_stage2_nodes_compared"] > 0
    assert [s.t for s in keep["samples"]] == [4.0, 5.0, 6.0, 7.0]
    assert keep["ref"].clients_per_node == 4


def test_stage2_fault_in_one_member_is_caught(monkeypatch):
    from repro.core.controller import NodeCacheArbiter
    apply_slots = NodeCacheArbiter.apply_slots

    def patched(self, values):
        # the second member gets the grid value next to its own
        values = list(values)
        grid = list(self.spaces.dirty_cache_mb)
        if len(self.members) > 1:
            i = grid.index(values[1])
            values[1] = grid[i - 1] if i else grid[1]
        return apply_slots(self, values)

    monkeypatch.setattr(NodeCacheArbiter, "apply_slots", patched)
    keep = {}
    res = cpu.run_small(four_to_a_node(monkeypatch), keep=keep)
    assert keep["numbers"]["_stage2_nodes_compared"] > 0
    assert res["correct"] is False
    assert res["checks"]["stage2_mismatch"]["value"] > 0
