"""The program's spans on the profiler's clock, and the fleet's
host<->device boundary in telemetry.

* An enabled :class:`Recorder` opens a ``jax.profiler.TraceAnnotation``
  of the same name around each span when jax is already imported, and
  none when it is disabled; the telemetry package still imports without
  jax.
* ``DeviceFleet`` records its host-side parts as ``fleet.*`` spans and
  counts every byte it moves (``fleet.h2d_bytes``, ``fleet.d2h_bytes``):
  a tuned fleet re-uploads statics and pulls its state back, an untuned
  one moves only the noise in and the activity mask out.
* Recording changes nothing: a telemetry-on ``soa-jax`` run is
  bit-identical to a telemetry-off one.
"""
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

from test_transport import SPACES, _fleet_sim, _models, _signature

from repro.core.policies.carat import CaratPolicy
from repro.core.runtime.telemetry.recorder import (NullRecorder, Recorder,
                                                   active, disable, enabled,
                                                   install)
from repro.storage import PFSParams, Simulation, WORKLOADS, get_workload

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_OSTS = 4


@pytest.fixture(autouse=True)
def _restore_recorder():
    prev = active()
    yield
    install(prev)


class _FakeAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs its use."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, exc[0]))
        return None


@pytest.fixture
def fake_jax(monkeypatch):
    _FakeAnnotation.log = []
    fake = types.ModuleType("jax")
    fake.profiler = types.SimpleNamespace(TraceAnnotation=_FakeAnnotation)
    monkeypatch.setitem(sys.modules, "jax", fake)
    return _FakeAnnotation.log


# ============================================ the recorder's profiler bridge
def test_enabled_span_opens_an_annotation_of_the_same_name(fake_jax):
    rec = Recorder(source="t", capacity=8)
    with rec.span("fleet.step", cat="fleet"):
        with rec.span("fleet.noise", cat="fleet"):
            pass
    with pytest.raises(KeyError):
        with rec.span("carat.score"):
            raise KeyError("x")
    assert fake_jax == [("enter", "fleet.step"), ("enter", "fleet.noise"),
                        ("exit", "fleet.noise", None),
                        ("exit", "fleet.step", None),
                        ("enter", "carat.score"),
                        ("exit", "carat.score", KeyError)]
    names = [ev.name for ev in rec.drain().spans]
    assert names == ["fleet.noise", "fleet.step", "carat.score"]


def test_disabled_recorder_opens_no_annotation(fake_jax):
    disable()
    rec = active()
    assert isinstance(rec, NullRecorder)
    with rec.span("fleet.step"):
        rec.count("fleet.h2d_bytes", 8.0)
    assert fake_jax == []


def test_recorder_built_before_jax_opens_no_annotation(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax", raising=False)
    rec = Recorder(source="t", capacity=8)
    with rec.span("policy.observe"):
        pass
    assert rec._annotation is None
    assert [ev.name for ev in rec.drain().spans] == ["policy.observe"]


def test_real_annotation_is_the_profilers():
    import jax
    assert Recorder()._annotation is jax.profiler.TraceAnnotation


def test_telemetry_imports_and_records_without_jax():
    script = textwrap.dedent("""
        import sys

        class _BlockJax:
            def find_spec(self, name, path=None, target=None):
                if name == "jax" or name.startswith("jax."):
                    raise ImportError(f"import of {name!r} blocked for test")
                return None

        sys.meta_path.insert(0, _BlockJax())
        from repro.core.runtime import telemetry
        with telemetry.enabled(source="t") as rec:
            with rec.span("fleet.step"):
                pass
        assert rec._annotation is None
        assert [e.name for e in rec.drain().spans] == ["fleet.step"]
        assert "jax" not in sys.modules
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


# ================================================ the fleet's boundary
def _carat_fleet(seed=11):
    sim = _fleet_sim(n_nodes=2, cpn=2, seed=seed, backend="soa-jax",
                     params=PFSParams(n_osts=N_OSTS))
    pol = sim.attach_policy(CaratPolicy(SPACES, _models(), backend="numpy"))
    return sim, pol


def _static_fleet(n=8):
    names = sorted(WORKLOADS)
    wls = [get_workload(names[i % len(names)]) for i in range(n)]
    return Simulation(wls, params=PFSParams(n_osts=N_OSTS), seed=2,
                      backend="soa-jax")


def _span_counts(rec):
    out = {}
    for ev in rec.drain().spans:
        out[ev.name] = out.get(ev.name, 0) + 1
    return out


def _host_bytes(tree):
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if isinstance(x, np.ndarray))


def test_tuned_fleet_counts_every_byte_it_moves(monkeypatch):
    from repro.storage import device
    sim, _ = _carat_fleet()
    fleet = sim.device_fleet
    moved = {"put": 0, "noise": 0}
    put = device.jax.device_put
    noise_for = sim.cluster._noise_for

    def counting_put(x, *a, **kw):
        moved["put"] += _host_bytes(x)
        return put(x, *a, **kw)

    def counting_noise(mask):
        noise = noise_for(mask)
        moved["noise"] += noise.nbytes
        return noise

    monkeypatch.setattr(device.jax, "device_put", counting_put)
    monkeypatch.setattr(sim.cluster, "_noise_for", counting_noise)
    steps = 12
    with enabled(source="t") as rec:
        for _ in range(steps):
            sim.step()
        counters = rec.snapshot()["counters"]
        spans = _span_counts(rec)
    assert spans["fleet.step"] == steps
    assert spans["fleet.statics"] >= 2          # the first and an actuation
    assert spans["fleet.sync_host"] >= 2
    assert spans["carat.score"] >= 1 and spans["carat.select"] >= 1
    assert spans["policy.stage2"] == steps      # recorded at every step
    assert counters["fleet.h2d_bytes"] == moved["put"] + moved["noise"]
    state = sum(x.nbytes for x in device.jax.tree.leaves(fleet._state))
    pulls = steps + spans.get("fleet.mask", 0)
    assert counters["fleet.d2h_bytes"] == (
        spans["fleet.sync_host"] * state + pulls * N_OSTS)


def test_untuned_fleet_moves_only_noise_and_mask():
    sim = _static_fleet()
    sim.step()                                  # first push and statics
    steps = 5
    with enabled(source="t") as rec:
        for _ in range(steps):
            sim.step()
        counters = rec.snapshot()["counters"]
        spans = _span_counts(rec)
    assert spans == {"fleet.step": steps, "fleet.noise": steps}
    assert counters == {"fleet.h2d_bytes": steps * N_OSTS * 8.0,
                        "fleet.d2h_bytes": steps * N_OSTS * 1.0}


def test_first_interval_pushes_state_and_statics():
    sim = _static_fleet()
    with enabled(source="t") as rec:
        sim.step()
        spans = _span_counts(rec)
    assert {"fleet.push", "fleet.statics", "fleet.mask",
            "fleet.noise", "fleet.step"} == set(spans)


def test_sharded_device_fleet_counts_noise_and_mask():
    from repro.core.runtime.sharded import ShardedRuntime
    sim = _static_fleet()
    rt = ShardedRuntime(sim, mode="sync", n_shards=2, device_map="auto")
    rt.run(1.0)                                 # push and statics
    with enabled(source="t") as rec:
        rt.run(1.0)
        counters = rec.snapshot()["counters"]
        spans = _span_counts(rec)
    steps = spans["fleet.step"]
    assert steps == 2
    assert spans["fleet.mask"] == spans["fleet.noise"] == steps
    assert "fleet.statics" not in spans and "fleet.push" not in spans
    assert counters["fleet.h2d_bytes"] == steps * N_OSTS * 8.0
    # the mask's count vector, pulled once per step, and the final sync
    assert counters["fleet.d2h_bytes"] >= steps * N_OSTS * 8.0


def test_telemetry_on_is_bit_identical_on_soa_jax():
    disable()
    sim_a, pol_a = _carat_fleet(seed=5)
    res_a = sim_a.run(8.0)
    sim_b, pol_b = _carat_fleet(seed=5)
    with enabled(source="t") as rec:
        res_b = sim_b.run(8.0)
    assert _span_counts(rec)["fleet.statics"] > 0
    assert pol_a.decision_count == pol_b.decision_count > 0
    assert _signature(sim_a, pol_a, res_a) == _signature(sim_b, pol_b, res_b)
    for op in ("read", "write"):
        for f in ("app_bytes", "rpc_count", "lat_sum_s"):
            assert np.array_equal(getattr(getattr(sim_a.core, op), f),
                                  getattr(getattr(sim_b.core, op), f))
    assert np.array_equal(sim_a.cluster.wait_s, sim_b.cluster.wait_s)


def test_spans_reach_the_profilers_host_plane(tmp_path):
    """With the profiler on, the fleet's spans appear as host-plane events
    of the trace, on the clock the device planes share."""
    import glob

    import jax
    from jax.profiler import ProfileData
    sim = _static_fleet()
    sim.step()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with enabled(source="t"):
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            sim.step()
        finally:
            jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {e.name for pl in ProfileData.from_file(path).planes
             if pl.name.startswith("/host:")
             for ln in pl.lines for e in ln.events}
    assert {"fleet.step", "fleet.noise"} <= names
