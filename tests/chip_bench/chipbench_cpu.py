"""Helpers of the benchmark's CPU self-tests: one run of a cell at a
small size on the CPU (the chip check skipped), and faults planted in
the timed path underneath it."""
import dataclasses
import io
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench import harness, spec  # noqa: E402

N_CLIENTS = 128
SECONDS = 1.5


def unlisted_cell(name, config, traffic, like):
    """A cell that ``BENCHMARK.json`` does not list, from its files: its
    configuration and traffic, with the limits (and, for phased jobs,
    ``member_mismatch`` 0) and metrics of the listed cell ``like``."""
    base = spec.load_cell(like)
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        cfg = json.load(f)
    t = spec.load_traffic(traffic)
    numbers = dict(base.limits["numbers"])
    if "schedule" in t:
        numbers["member_mismatch"] = 0
    return dataclasses.replace(
        base, name=name, config=cfg, traffic=t, limits={"numbers": numbers},
        references=spec.load_references(cfg))


def run_small(cell, seed=20261016, keep=None, trace=False):
    """One small run of ``cell``, a cell's name or a loaded cell."""
    if isinstance(cell, str):
        cell = spec.load_cell(cell)
    return harness.run(cell, seed, SECONDS, trace, time.perf_counter(),
                       require_tpu=False, n_clients=N_CLIENTS,
                       log=io.StringIO(), keep=keep, compile_cache=False)


def break_fleet_step(monkeypatch, kind):
    """Plant a fault in the fused fleet step: ``unchanged`` returns the
    state it was given, ``half`` steps only the first half of the
    clients, ``altered`` adds a MiB to one client's written bytes."""
    import jax
    import jax.numpy as jnp
    from repro.storage import device
    build = device.DeviceFleet._build_step

    def patched(self):
        real = build(self)
        n = self.core.n

        def step(state, s, t, dt, noise):
            old = jax.tree.map(lambda a: jnp.array(a, copy=True), state)
            new, totals, mask = real(state, s, t, dt, noise)
            if kind == "unchanged":
                new = dict(old, act=new["act"])
            elif kind == "half":
                keep = jnp.arange(n) < n // 2
                new = jax.tree.map(
                    lambda a, b: jnp.where(keep, a, b)
                    if a.shape[:1] == (n,) else a, new, old)
            elif kind == "altered":
                w = dict(new["write"])
                w["app_bytes"] = w["app_bytes"].at[0].add(float(1 << 20))
                new = dict(new, write=w)
            else:
                raise ValueError(kind)
            return new, totals, mask
        return step

    monkeypatch.setattr(device.DeviceFleet, "_build_step", patched)


def break_scores(monkeypatch):
    """Alter one probability where the scorer produces it."""
    from repro.kernels.gbdt_infer import ops
    call = ops.GridGBDTScorer.__call__

    def patched(self, H, backend=None):
        out = np.array(call(self, H, backend), dtype=np.float64)
        out[0, 0] = min(out[0, 0] + 0.01, 1.0) if out[0, 0] < 0.99 \
            else out[0, 0] - 0.01
        return out

    monkeypatch.setattr(ops.GridGBDTScorer, "__call__", patched)


def break_decisions(monkeypatch):
    """Alter the first client's choice where Algorithm 1 makes it."""
    from repro.core import rpc_tuner
    select = rpc_tuner.ConditionalScoreGreedy._select_many

    def patched(self, ops_, probs, rngs=None):
        out = np.array(select(self, ops_, probs, rngs))
        if out.size:
            out[0] = (out[0] + 1) % probs.shape[1]
        return out

    monkeypatch.setattr(rpc_tuner.ConditionalScoreGreedy, "_select_many",
                        patched)


def skip_half_of_the_due_clients(monkeypatch):
    """The controller of every odd client observes but never asks for its
    due decision, so the policy decides only half of the clients due."""
    from repro.core.controller import CaratController
    observe = CaratController.observe

    def patched(self, client, t, dt):
        req = observe(self, client, t, dt)
        return None if self.client_id % 2 else req

    monkeypatch.setattr(CaratController, "observe", patched)


def alter_features(monkeypatch):
    """Alter one feature of client 0's row where the probe produces it."""
    from repro.core.controller import CaratController
    observe = CaratController.observe

    def patched(self, client, t, dt):
        req = observe(self, client, t, dt)
        if req is not None and self.client_id == 0:
            feats = np.array(req[1], dtype=np.float32)
            feats[3] += np.float32(0.01)
            req = (req[0], feats)
        return req

    monkeypatch.setattr(CaratController, "observe", patched)


def control_checks(cell, keep):
    """The control's numbers, put in the program's place, judged by the
    cell's limits."""
    from chipbench import check
    if isinstance(cell, str):
        cell = spec.load_cell(cell)
    nums = check.fleet_numbers(keep["ref"], keep["samples"],
                               dtype=np.float32, use_program=False)
    if cell.is_carat:
        nums.update(check.tuner_numbers(keep["ref"], keep["samples"],
                                        use_program=False))
    return check.judge(nums, cell.limits)
