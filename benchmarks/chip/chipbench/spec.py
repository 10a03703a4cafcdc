"""Find a cell's pieces by name: its configuration, traffic mix, limits,
per-layer readers and the chip's peaks.

``BENCHMARK.json`` at the checkout's root names each cell's
configuration and traffic; each of those is a file of its own under this
benchmark's directory (``configs/<config>.json``,
``traffic/<traffic>.json``, ``limits/<cell>.json``,
``metrics/<metric>.py``), so a cell, a configuration, a mix or a
per-layer metric is added by adding files, never by editing one. A
traffic file names the policy and the warm-up; the workload members it
runs live in ``mixes/<mix>.json``, which several traffic files share, so
cells that differ only in policy run the very same traffic.

Three optional keys let a deployment that needs more than a private node
per client, one workload per client and the stock references be added
with files alone; a file that leaves them out runs the plain path:

* a configuration's ``clients_per_node`` (default 1): k > 1 puts clients
  jk ... jk+k-1 on node j, whose stage-2 arbiter shares one cache budget
  among its k members; the file's ``assumed`` says where k comes from;
* a configuration's ``references``: ``{"fleet": <module>, "tuner":
  <module>}``, modules under ``chipbench/`` that stand in for
  ``fleet_ref`` and ``tuner_ref`` (a deployment with a fleet mechanism of
  its own brings its own copy of the reference);
* a mix's ``schedule``: phased jobs, each a run of ``job_clients``
  consecutive clients that steps through one of the named ``sequences``
  of members, ``segment_s`` simulated seconds per member, repeating
  without end (``chipbench/traffic.py`` draws each job's sequence and
  starting phase from the seed).
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field
from types import ModuleType
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


class SpecError(ValueError):
    """A cell, configuration, mix or peak entry that cannot be found."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {path}") from None


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str = BENCH_DIR
    # role ("fleet", "tuner") -> the reference module the check calls
    references: Dict[str, ModuleType] = field(default_factory=dict)

    @property
    def is_carat(self) -> bool:
        return self.traffic["policy"]["name"] == "carat"


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json (have "
                        f"{sorted(cells)})")
    w = cells[name]
    config = _load_json(os.path.join(bench_dir, "configs",
                                     w["config"] + ".json"))
    return Cell(
        name=name,
        config=config,
        traffic=load_traffic(w["traffic"], bench_dir),
        limits=_load_json(os.path.join(bench_dir, "limits", name + ".json")),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir,
        references=load_references(config, bench_dir))


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    """``traffic/<name>.json`` with the members of the mix it names."""
    t = _load_json(os.path.join(bench_dir, "traffic", name + ".json"))
    mix = _load_json(os.path.join(bench_dir, "mixes", t["mix"] + ".json"))
    both = (set(mix) & set(t)) - {"name"}
    if both:
        raise SpecError(f"traffic {name!r} and mix {t['mix']!r} both set "
                        f"{sorted(both)}")
    if "schedule" in mix:
        _check_schedule(mix)
    return dict(mix, **t)


SCHEDULE_KEYS = {"sequences", "segment_s", "job_clients", "start"}


def _check_schedule(mix: dict) -> None:
    sched = mix["schedule"]
    where = f"mix {mix['name']!r}'s schedule"
    if set(sched) != SCHEDULE_KEYS:
        raise SpecError(f"{where} has keys {sorted(sched)}, not "
                        f"{sorted(SCHEDULE_KEYS)}")
    if sched["start"] != "whole_segment":
        raise SpecError(f"{where}: start {sched['start']!r} is not "
                        f"'whole_segment'")
    if not (sched["segment_s"] > 0 and sched["job_clients"] >= 1
            and sched["sequences"]):
        raise SpecError(f"{where} needs segment_s > 0, job_clients >= 1 "
                        f"and a sequence")
    names = {m["name"] for m in mix["members"]}
    for seq, members in sched["sequences"].items():
        unknown = [m for m in members if m not in names]
        if not members or unknown:
            raise SpecError(f"{where}: sequence {seq!r} names no member or "
                            f"unknown ones {unknown}")


REFERENCE_ROLES = ("fleet", "tuner")


def load_references(config: dict,
                    bench_dir: str = BENCH_DIR) -> Dict[str, ModuleType]:
    """The reference modules a configuration names (``references``), each
    ``chipbench/<module>.py`` under ``bench_dir``; a role it leaves out
    is ``fleet_ref`` or ``tuner_ref``."""
    from chipbench import fleet_ref, tuner_ref
    named = config.get("references", {})
    unknown = set(named) - set(REFERENCE_ROLES)
    if unknown:
        raise SpecError(f"references {sorted(unknown)} of configuration "
                        f"{config.get('name')!r} are none of "
                        f"{REFERENCE_ROLES}")
    out = {"fleet": fleet_ref, "tuner": tuner_ref}
    for role, module in named.items():
        if not module.isidentifier():
            raise SpecError(f"{role} reference {module!r} is not a module "
                            f"name")
        out[role] = _load_module(
            os.path.join(bench_dir, "chipbench", module + ".py"),
            f"chipbench_ref_{module}", f"{role} reference {module!r}")
    return out


def _load_module(path: str, module_name: str, what: str) -> ModuleType:
    if not os.path.exists(path):
        raise SpecError(f"no file {path} for {what}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    """This chip's peaks; a ``device_kind`` missing from the table is an
    error, never a default."""
    table = _load_json(os.path.join(bench_dir, "peaks.json"))
    kinds = table["device_kinds"]
    if device_kind not in kinds:
        raise SpecError(f"device_kind {device_kind!r} is not in peaks.json "
                        f"(have {sorted(kinds)})")
    return kinds[device_kind]


def load_reader(metric: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``read(run)`` of ``metrics/<metric>.py``."""
    return _load_module(
        os.path.join(bench_dir, "metrics", metric + ".py"),
        f"chipbench_metric_{metric.replace('.', '_')}",
        f"per-layer metric {metric!r}").read
