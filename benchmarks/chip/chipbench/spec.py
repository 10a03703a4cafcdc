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
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, List

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
    return Cell(
        name=name,
        config=_load_json(os.path.join(bench_dir, "configs",
                                       w["config"] + ".json")),
        traffic=load_traffic(w["traffic"], bench_dir),
        limits=_load_json(os.path.join(bench_dir, "limits", name + ".json")),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir)


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    """``traffic/<name>.json`` with the members of the mix it names."""
    t = _load_json(os.path.join(bench_dir, "traffic", name + ".json"))
    mix = _load_json(os.path.join(bench_dir, "mixes", t["mix"] + ".json"))
    both = (set(mix) & set(t)) - {"name"}
    if both:
        raise SpecError(f"traffic {name!r} and mix {t['mix']!r} both set "
                        f"{sorted(both)}")
    return dict(mix, **t)


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
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no reader {path} for per-layer metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
