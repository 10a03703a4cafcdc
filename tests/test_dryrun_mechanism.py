"""The dry-run mechanism, validated on a small mesh in a subprocess.

The full 512-device production sweep lives in launch/dryrun.py (results in
dryrun_results/); this test proves the machinery — forced host devices,
mesh construction, sharded lower+compile, roofline extraction — on an
8-device mesh with a reduced arch, in an isolated process so XLA_FLAGS
never leak into the test session.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.config import get_arch, reduced_config
    from repro.config.types import ParallelConfig, RunConfig, ShapeConfig
    from repro.launch.input_specs import train_batch_specs
    from repro.models.lm import build_model
    from repro.parallel.constraints import default_rules, set_activation_rules
    from repro.parallel.sharding import (batch_pspec, param_pspecs,
                                         sanitized_shardings as _shardings)
    from repro.roofline.analysis import analyze_compiled
    from repro.train.state import TrainState
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import make_train_step

    assert jax.device_count() == 8
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = reduced_config(get_arch("granite-3-2b"))
    shape = ShapeConfig("tiny", 64, 8, "train")
    par = ParallelConfig(fsdp=True, remat="dots")
    run = RunConfig(arch=cfg, shape=shape, parallel=par)
    model = build_model(cfg)
    set_activation_rules(default_rules(mesh))

    params_abs = model.abstract_params()
    p_sh = _shardings(params_abs, param_pspecs(model, par), mesh)
    batch_abs = train_batch_specs(cfg, shape)
    b_sh = _shardings(batch_abs, batch_pspec(cfg, shape, mesh), mesh)
    state_abs = {
        "params": params_abs,
        "opt": {"m": params_abs, "v": params_abs,
                "count": jax.ShapeDtypeStruct((), jnp.int32)},
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    state_sh = {"params": p_sh,
                "opt": {"m": p_sh, "v": p_sh,
                        "count": NamedSharding(mesh, P())},
                "step": NamedSharding(mesh, P())}
    step = make_train_step(model, run)
    with mesh:
        lowered = jax.jit(step, in_shardings=(state_sh, b_sh)).lower(
            state_abs, batch_abs)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    report = analyze_compiled(compiled, None, cfg.name, shape.name,
                              "mesh2x4", 8, model_flops=1.0)
    print(json.dumps({
        "temp_bytes": mem.temp_size_in_bytes,
        "flops": report.flops_per_device,
        "collective_bytes": report.collective_bytes_per_device,
        "bottleneck": report.bottleneck,
    }))
""")


@pytest.mark.slow
def test_dryrun_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["temp_bytes"] > 0
    assert rec["collective_bytes"] > 0     # sharded program must communicate
    assert rec["bottleneck"] in ("compute", "memory", "collective")


def test_production_sweep_results_exist():
    """The committed production dry-run must cover every cell."""
    results = os.path.join(REPO, "dryrun_results")
    if not os.path.isdir(results) or not os.listdir(results):
        pytest.skip("production sweep not yet run (launch.dryrun --all)")
    files = [f for f in os.listdir(results) if f.endswith(".json")]
    # 10 archs x 4 shapes x 2 meshes = 80 records (skips included as records)
    assert len(files) >= 60
    ok = skipped = failed = 0
    for f in files:
        with open(os.path.join(results, f)) as fh:
            r = json.load(fh)
        if r.get("status") == "ok":
            ok += 1
            assert r["flops_per_device"] > 0
        elif r.get("status") == "skipped":
            skipped += 1
        else:
            failed += 1
    assert failed == 0
    assert ok >= 50
