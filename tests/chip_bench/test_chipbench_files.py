"""The on-chip benchmark's data files, entry point and arithmetic, on the
CPU: every cell's pieces load by name, the entry point refuses to run
without a TPU or on a chip missing from the peaks table, and the GBDT
work count is what its formula says."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench import spec, traffic, work  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
METRICS = [m["name"] for m in BENCHMARK["per_layer"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_load_by_name(name):
    cell = spec.load_cell(name)
    assert cell.config["n_clients"] > 0 and cell.config["n_osts"] > 0
    assert cell.traffic["members"] and cell.traffic["policy"]["name"]
    assert set(cell.limits["numbers"]) >= {"fleet_rel_err", "noise_draws"}
    if cell.is_carat:
        assert "gbdt_max_dp" in cell.limits["numbers"]
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert cell.per_layer


@pytest.mark.parametrize("entry", BENCHMARK["configs"],
                         ids=[c["name"] for c in BENCHMARK["configs"]])
def test_config_file_names_its_cuts(entry):
    with open(os.path.join(REPO, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["name"] == entry["name"]
    assert set(entry["reduced"]) == set(cfg["reduced"])
    for key in entry["reduced"]:
        assert key in cfg
    # the optional keys: shared nodes say where k comes from, and each
    # named reference is a module of the benchmark's own
    assert cfg["clients_per_node"] >= 1
    if cfg["clients_per_node"] > 1:
        assert cfg["assumed"].get("clients_per_node")
    assert set(cfg.get("references", {})) <= set(spec.REFERENCE_ROLES)
    for module in cfg.get("references", {}).values():
        assert os.path.exists(os.path.join(BENCH, "chipbench",
                                           module + ".py"))


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_loads_by_name(name):
    read = spec.load_reader(name)
    empty = SimpleNamespace(trace=None, spans_s={}, intervals=0,
                            decision_intervals=0, scorer_rows=[], models={},
                            compiles_in_window=0)
    assert name == "compiles_in_window" or read(empty) is None


def test_traffic_files_share_their_mix_by_name():
    # cells that differ only in policy run one mix file's members
    tdir = os.path.join(BENCH, "traffic")
    for name in sorted(os.listdir(tdir)):
        with open(os.path.join(tdir, name)) as f:
            t = json.load(f)
        assert "members" not in t, name
        assert os.path.exists(os.path.join(BENCH, "mixes",
                                           t["mix"] + ".json")), name
    a = spec.load_traffic("carat_striped")
    b = spec.load_traffic("static_striped")
    assert a["members"] == b["members"] and a["mix"] == b["mix"]
    assert a["policy"]["name"] != b["policy"]["name"]


def test_missing_pieces_are_errors():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no_such_config.no_such_mix")
    with pytest.raises(spec.SpecError):
        spec.load_reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.load_references({"references": {"fleet": "no_such_ref"}})
    with pytest.raises(spec.SpecError):
        spec.load_references({"references": {"planner": "fleet_ref"}})


@pytest.mark.parametrize("bad", [
    {"segment_s": 0.0}, {"start": "free_offset"}, {"job_clients": 0},
    {"sequences": {"read_seq": ["no_such_member"]}}, {"extra": 1}])
def test_a_malformed_schedule_is_an_error(bad):
    mix = {"name": "m", "members": [{"name": "a"}], "schedule": dict(
        {"sequences": {"q": ["a"]}, "segment_s": 20.0, "job_clients": 4,
         "start": "whole_segment"}, **bad)}
    with pytest.raises(spec.SpecError):
        spec._check_schedule(mix)


# The cells that run the plain path: no shared nodes, no phased jobs, the
# stock references, and the traffic draws they had before these keys
# existed (digests of generate()'s arrays for fixed seeds).
PLAIN_CELLS = ["frontier_9408.carat_striped", "frontier_9408.static_striped",
               "fugaku_158976.static_striped"]
DRAWS = [
    (9408, 448, 2**31 + 7,
     "fdaec66c7f0428dc809892314901645adc590b641d45579db3c1f8096dabd46d"),
    (79488, 224, 2147480005,
     "903527b38e9b6a4d8a1defa8c0c025e6fffdfe09b3cc30ff9a3f5ebbadc2780f"),
    (128, 448, 20261016,
     "3a4f84eca36153cf80a449628dbaf9928ff5bcda6304a0f92572d1cd00ddfb22"),
]


@pytest.mark.parametrize("name", PLAIN_CELLS)
def test_plain_cells_build_the_plain_fleet(name):
    from chipbench import fleet_ref, harness, tuner_ref
    cell = spec.load_cell(name)
    assert cell.references == {"fleet": fleet_ref, "tuner": tuner_ref}
    sim, inputs, sched = harness.build(cell, 64, 7)
    assert sched is None and sim.topology is None
    assert sim.policies() == []
    members = [m["name"] for m in cell.traffic["members"]]
    assert [c.workload.name for c in sim.clients] == \
        [members[i] for i in inputs.member_idx]


@pytest.mark.parametrize("n,n_osts,seed,digest", DRAWS)
def test_traffic_draws_are_unchanged(n, n_osts, seed, digest):
    import hashlib
    x = traffic.generate(n, n_osts, 8, seed)
    got = hashlib.sha256(x.member_idx.tobytes() + x.stripe_offsets.tobytes()
                         + str(x.sim_seed).encode()).hexdigest()
    assert got == digest


def test_schedule_gives_equal_shares_and_whole_segment_switches():
    mix = spec.load_traffic("carat_fig7")
    plan, names = mix["schedule"], [m["name"] for m in mix["members"]]
    a = traffic.schedule(9408, plan, names, 2**31 + 11)
    b = traffic.schedule(9408, plan, names, 2**31 + 11)
    c = traffic.schedule(9408, plan, names, 12)
    assert np.array_equal(a.client_start, b.client_start)
    assert np.array_equal(a.client_seq, b.client_seq)
    assert not np.array_equal(a.client_seq, c.client_seq)
    for x in (a, c):
        # 73 jobs of 128 and one of 64; 25/25/24 jobs per sequence
        jobs = np.bincount(x.job_of)
        assert jobs.size == 74 and set(jobs[:-1]) == {128} and jobs[-1] == 64
        job_seq = x.client_seq[np.searchsorted(x.job_of, np.arange(74))]
        assert np.bincount(job_seq).tolist() == [25, 25, 24]
        job_start = x.client_start[np.searchsorted(x.job_of, np.arange(74))]
        for q in range(3):
            shares = np.bincount(job_start[job_seq == q], minlength=4)
            assert shares.max() - shares.min() <= 1
    # members change only at the multiples of segment_s, every job at once
    seg = plan["segment_s"]
    assert np.array_equal(a.member_at(0.0), a.member_at(seg - 0.5))
    assert np.all(a.member_at(seg) != a.member_at(seg - 0.5))
    assert np.array_equal(a.member_at(4 * seg), a.member_at(0.0))
    assert a.next_switch(80.5) == 100.0 and a.next_switch(100.0) == 100.0


def test_peaks_table_is_keyed_by_device_kind():
    assert spec.load_peaks("TPU v5 lite") == {
        "flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v99")


def test_gbdt_work_count():
    # 10 clients x 63 candidates x 200 trees x (5 levels + 1 add)
    assert work.gbdt_ops(10, 63, 200, 5) == 10 * 63 * 200 * 6
    nbytes = work.gbdt_bytes(10, 63, 200, 5, 20, 2)
    assert nbytes == (10 * 20 + 63 * 2) * 4 + 200 * 5 * 8 + 200 * 32 * 4 + 4
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.min_seconds(4e12, 1e9, peaks) == 4.0      # compute bound
    assert work.min_seconds(1e9, 3e9, peaks) == 3.0       # memory bound


def test_traffic_gives_every_seed_equal_shares():
    a = traffic.generate(1000, 448, 8, 2**31 + 7)
    b = traffic.generate(1000, 448, 8, 2**31 + 7)
    c = traffic.generate(1000, 448, 8, 5)
    assert np.array_equal(a.member_idx, b.member_idx)
    assert a.sim_seed == b.sim_seed
    assert not np.array_equal(a.member_idx, c.member_idx)
    for x in (a, c):
        assert np.bincount(x.member_idx).tolist() == [125] * 8
        assert 0 <= x.stripe_offsets.min() and x.stripe_offsets.max() < 448


def _run_entry(env_extra, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)


def test_entry_point_exits_nonzero_without_a_tpu():
    p = _run_entry({}, "--workload", CELLS[0], "--seed", "1",
                   "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_entry_point_refuses_an_unknown_device_kind(monkeypatch, capsys):
    import run as entry
    from chipbench import harness
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(harness, "_devices", lambda chips, req: [fake])
    rc = entry.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "TPU v99" in out.err


def test_entry_point_refuses_a_tree_without_the_program(tmp_path):
    # a checkout holding only BENCHMARK.json and the benchmark's files
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
