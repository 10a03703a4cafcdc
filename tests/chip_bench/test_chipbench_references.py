"""References named by a configuration: a configuration that names a copy
of ``fleet_ref`` or ``tuner_ref`` runs the check through that copy and
reads the same numbers as the stock reference; one that names a module
the benchmark does not have is refused."""
import dataclasses
import json
import os
import shutil

import pytest

import chipbench_cpu as cpu
from chipbench import check, fleet_ref, spec, tuner_ref

ROLES = {"fleet": ("fleet_ref", fleet_ref, "frontier_9408.static_striped"),
         "tuner": ("tuner_ref", tuner_ref, "frontier_9408.carat_striped")}


def _bench_naming(tmp_path, role, module):
    """A checkout whose Frontier configuration names ``module`` for
    ``role``, with a copy of the stock reference under that name."""
    bench = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "traffic", "mixes", "limits", "models"):
        shutil.copytree(os.path.join(cpu.BENCH, d), bench / d)
    (bench / "chipbench").mkdir()
    shutil.copy(os.path.join(cpu.BENCH, "chipbench", ROLES[role][0] + ".py"),
                bench / "chipbench" / f"{ROLES[role][0]}_copy.py")
    shutil.copy(os.path.join(cpu.REPO, "BENCHMARK.json"), tmp_path)
    path = bench / "configs" / "frontier_9408.json"
    cfg = json.loads(path.read_text())
    cfg["references"] = {role: module}
    path.write_text(json.dumps(cfg))
    return str(tmp_path), str(bench)


@pytest.mark.parametrize("role", sorted(ROLES))
def test_named_copy_gives_the_same_numbers(tmp_path, role):
    stock_name, stock, cell_name = ROLES[role]
    root, bench = _bench_naming(tmp_path, role, stock_name + "_copy")
    cell = spec.load_cell(cell_name, root=root, bench_dir=bench)
    named = cell.references[role]
    assert named is not stock
    assert named.__file__ == os.path.join(bench, "chipbench",
                                          stock_name + "_copy.py")
    keep = {}
    res = cpu.run_small(cell, keep=keep)
    assert res["correct"] is True, res["checks"]
    ref = keep["ref"]
    assert getattr(ref, role) is named
    plain = dataclasses.replace(ref, **{role: stock})
    numbers = (check.fleet_numbers if role == "fleet"
               else check.tuner_numbers)
    assert numbers(ref, keep["samples"]) == numbers(plain, keep["samples"])


def test_missing_reference_module_is_refused(tmp_path):
    root, bench = _bench_naming(tmp_path, "fleet", "no_such_ref")
    with pytest.raises(spec.SpecError, match="no_such_ref"):
        spec.load_cell("frontier_9408.static_striped", root=root,
                       bench_dir=bench)
