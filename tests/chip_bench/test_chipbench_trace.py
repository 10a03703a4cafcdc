"""The trace reduction: checked on synthetic planes whose answers are
known by hand, and on a small trace recorded on a TPU v5e (a 64-client
CARAT fleet, two intervals) against a plain recount of its events."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench import trace  # noqa: E402

RECORDED = os.path.join(BENCH, "testdata", "small_carat.xplane.pb")


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def synthetic():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.interval", 0, 1000), ev("bench.fleet_step", 0, 300),
        ev("bench.interval", 1000, 1000), ev("bench.decide", 1500, 400)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_step(123)", 100, 150),
                                       ev("jit_step(123)", 1100, 150),
                                       ev("jit_gbdt(9)", 1600, 100)]),
        NS(name="XLA Ops", events=[
            ev("%while.1 = f32[2] while(f32[2] %a)", 100, 150),
            ev("%fusion.3 = f32[2] fusion(f32[2] %b)", 120, 50),
            ev("%while.1 = f32[2] while(f32[2] %a)", 1100, 150),
            ev("%gbdt.1 = f32[1,128] custom-call(f32[128,8] %x)", 1600,
               100),
            ev("%late.1 = f32[2] add(f32[2] %c)", 2500, 10)])])
    return [host, dev]


def test_synthetic_planes():
    r = trace.reduce_planes(synthetic())
    assert r.window_s == pytest.approx(2000e-9)
    # union: [100,250) + [1100,1250) + [1600,1700); the op after the
    # window does not count
    assert r.busy_s == pytest.approx(400e-9)
    assert r.n_intervals == 2 and r.n_devices == 1
    assert r.module_s == pytest.approx({"jit_step": 300e-9,
                                        "jit_gbdt": 100e-9})
    assert r.op_s["jit_step/while.1"] == pytest.approx(300e-9)
    assert r.op_s["jit_step/fusion.3"] == pytest.approx(50e-9)
    assert r.op_time("gbdt") == pytest.approx(100e-9)
    assert "?/late.1" not in r.op_s
    # the longest idle gap [250, 1100) sits in interval 1 after the step
    label, secs = r.gaps[0]
    assert label == "bench.interval" and secs == pytest.approx(850e-9)
    labels = dict((round(s * 1e9), n) for n, s in r.gaps)
    assert labels[100] == "bench.fleet_step"      # [0, 100)
    assert labels[350] == "bench.interval"        # [1250, 1600)
    b = r.breakdown(top=2)
    assert b["device_ops"][0][0] == "jit_step/while.1"
    assert len(b["idle_gaps"]) == 2


def test_union_length_merges_overlaps():
    assert trace.union_length([(0, 5), (3, 8), (10, 12), (11, 11.5)])[0] \
        == 10


def test_names():
    assert trace.op_name("%while.75 = (s32[]) while(...)") == "while.75"
    assert trace.module_name("jit_step(6940627993278059477)") == "jit_step"
    assert trace.module_name("jit_step") == "jit_step"


def test_recorded_trace_matches_a_plain_recount():
    from jax.profiler import ProfileData
    data = ProfileData.from_file(RECORDED)
    planes = list(data.planes)
    r = trace.reduce_planes(planes)
    host = [e for pl in planes if pl.name.startswith("/host:")
            for ln in pl.lines for e in ln.events
            if e.name == "bench.interval"]
    lo = min(e.start_ns for e in host)
    hi = max(e.start_ns + e.duration_ns for e in host)
    dev = [pl for pl in planes if pl.name == "/device:TPU:0"][0]
    lines = {ln.name: list(ln.events) for ln in dev.lines}
    ops = [e for e in lines["XLA Ops"] if lo <= e.start_ns < hi]
    kernel = sum(e.duration_ns for e in ops
                 if e.name.startswith("%gbdt_logits_pallas"))
    step = sum(e.duration_ns for e in lines["XLA Modules"]
               if lo <= e.start_ns < hi and e.name.startswith("jit_step("))
    assert r.n_intervals == len(host) == 2
    assert r.window_s == pytest.approx((hi - lo) * 1e-9)
    assert r.op_time("gbdt_logits_pallas") == pytest.approx(kernel * 1e-9)
    assert kernel > 0 and step > 0
    assert r.module_s["jit_step"] == pytest.approx(step * 1e-9)
    # busy: a sweep over the clipped op edges, counting open ops
    edges = []
    for e in ops:
        s = max(e.start_ns, lo)
        t = min(e.start_ns + e.duration_ns, hi)
        if t > s:
            edges += [(s, 1), (t, -1)]
    busy, depth, last = 0.0, 0, None
    for x, step_ in sorted(edges, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += x - last
        depth += step_
        last = x
    assert r.busy_s == pytest.approx(busy * 1e-9)
    assert 0 < r.busy_s < r.window_s
