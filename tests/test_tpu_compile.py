"""Compile rehearsals for a described TPU v5e chip, with no chip attached.

The TPU compiler is installed wherever jax[tpu] is, and compiles for a
chip described by ``topologies.get_topology_desc``. These tests compile
the main path's device programs — the GBDT Pallas kernel at production
shapes and the fused fleet step — so Mosaic or XLA:TPU refusing one
fails here, not on the chip. Nothing runs: a pass says the programs
compile and fit, not that they are right or fast.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file. The persistent compilation
cache is off here — a compile for a described device is written but
cannot be read back without one.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _production_model(n_trees=400, depth=5, n_features=22, seed=0):
    """A random ensemble at the production GBDT's shapes (400 trees of
    depth 5 over the 22 snapshot+candidate features)."""
    from repro.core.ml.gbdt import ObliviousGBDT
    rng = np.random.default_rng(seed)
    return ObliviousGBDT(
        feat=rng.integers(0, n_features, (n_trees, depth)).astype(np.int32),
        thr=rng.normal(size=(n_trees, depth)).astype(np.float32),
        leaf=rng.normal(size=(n_trees, 1 << depth)).astype(np.float32),
        base=0.1, n_features=n_features)


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_gbdt_kernel_compiles_for_v5e(one_chip, x64):
    """16,384 clients x 63 candidates of kernel rows, block_n 128. With
    x64 on too: the soa-jax backend enables it process-wide before the
    scorer ever runs."""
    from repro.kernels.gbdt_infer.kernel import gbdt_logits_pallas
    from repro.kernels.gbdt_infer.ops import pack_gbdt
    packed = pack_gbdt(_production_model())
    assert (packed.t_pad, packed.f_pad) == (512, 24)
    n = 16_384 * 63

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with jax.enable_x64(x64):
        compiled = gbdt_logits_pallas.lower(
            spec((n, packed.f_pad)), spec(packed.sel.shape),
            spec(packed.thr.shape), spec(packed.leaf_t.shape), spec(()),
            block_n=128, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_gbdt_cross_rows_compile_for_v5e(one_chip, x64):
    """The grid scorer's device-built kernel rows at the CARAT cell's
    bucket (5,120 clients x 63 candidates, 24 padded features)."""
    from repro.kernels.gbdt_infer.ops import _cross_rows, bucket_clients
    n_pad = bucket_clients(4704)

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    with jax.enable_x64(x64):
        compiled = _cross_rows.lower(spec((n_pad, 20)), spec((63, 2)),
                                     f_pad=24).compile()
    out = compiled.out_info
    assert out.shape == (n_pad * 63, 24) and out.dtype == jnp.float32


def test_fused_fleet_step_compiles_for_v5e(one_chip):
    """The fused plan+resolve+commit step of ``DeviceFleet`` over the
    striped mix, widened from a 16-client fleet's pytrees to 4,096
    clients as shapes only."""
    from repro.storage import Simulation
    from repro.storage.device import _statics_at
    from repro.storage.workloads import striped_fleet
    n0, n = 16, 4096
    sim = Simulation(striped_fleet(n0), seed=1, backend="soa-jax")
    core, fleet = sim.core, sim.device_fleet
    core._ensure_static()
    statics = _statics_at(core, slice(None))
    kmax = statics["ch_ost"].shape[1]
    assert len({n0, n0 * kmax, core.p.n_osts}) == 3   # dims stay distinct

    def widen(a):
        a = np.asarray(a)
        shape = tuple(n if d == n0 else n * kmax if d == n0 * kmax else d
                      for d in a.shape)
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=one_chip)

    state = jax.tree.map(widen, fleet._host_state())
    noise = jax.ShapeDtypeStruct((core.p.n_osts,), np.float64,
                                 sharding=one_chip)
    compiled = fleet._step_fn.lower(
        state, jax.tree.map(widen, statics), 1.0, sim.interval_s,
        noise).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)
