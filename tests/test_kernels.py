"""Per-kernel correctness: shape/dtype sweeps + hypothesis vs oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.ml.gbdt import train_gbdt
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import (flash_attention_ref,
                                               flash_attention_xla_chunked)
from repro.kernels.gbdt_infer.ops import (GridGBDTScorer, PallasGBDTScorer,
                                          bucket_clients, gbdt_predict_proba,
                                          pack_gbdt)


# ----------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (1, 4, 4, 128, 32),      # MHA
    (2, 8, 2, 256, 64),      # GQA 4:1
    (1, 8, 1, 128, 64),      # MQA
    (2, 4, 4, 192, 16),      # non-pow2 seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(b, hq, hkv, s, d, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (b, hq, s, d), dtype)
    k = jax.random.normal(k2, (b, hkv, s, d), dtype)
    v = jax.random.normal(k3, (b, hkv, s, d), dtype)
    ref = flash_attention_ref(q, k, v, causal=True)
    pal = flash_attention(q, k, v, causal=True, backend="pallas",
                          block_q=64, block_k=64)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(pal, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 32),
                                           (False, 0)])
def test_flash_attention_masks(causal, window):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (1, 2, 128, 32), jnp.float32)
    k = jax.random.normal(k2, (1, 2, 128, 32), jnp.float32)
    v = jax.random.normal(k3, (1, 2, 128, 32), jnp.float32)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    pal = flash_attention(q, k, v, causal=causal, window=window,
                          backend="pallas", block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=2e-5)


def test_chunked_xla_matches_exact():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (2, 4, 256, 32), jnp.float32)
    k = jax.random.normal(k2, (2, 2, 256, 32), jnp.float32)
    v = jax.random.normal(k3, (2, 2, 256, 32), jnp.float32)
    for causal, window in [(True, 0), (True, 64), (False, 0)]:
        ref = flash_attention_ref(q, k, v, causal=causal, window=window)
        chk = flash_attention_xla_chunked(q, k, v, causal=causal,
                                          window=window, block_k=64)
        np.testing.assert_allclose(np.asarray(chk), np.asarray(ref),
                                   atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(
    s=st.sampled_from([64, 128, 256]),
    hq=st.sampled_from([2, 4, 8]),
    ratio=st.sampled_from([1, 2]),
    seed=st.integers(0, 100),
)
def test_flash_attention_property(s, hq, ratio, seed):
    hkv = hq // ratio
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(k1, (1, hq, s, 32), jnp.float32)
    k = jax.random.normal(k2, (1, hkv, s, 32), jnp.float32)
    v = jax.random.normal(k3, (1, hkv, s, 32), jnp.float32)
    ref = flash_attention_ref(q, k, v)
    pal = flash_attention(q, k, v, backend="pallas", block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=3e-5)


# ----------------------------------------------------------- decode attention
@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 4, 4, 512, 32),
    (3, 8, 2, 1024, 64),
    (1, 8, 1, 256, 64),
])
def test_decode_attention_shapes(b, hq, hkv, s, d):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k1, (b, hq, d), jnp.float32)
    k = jax.random.normal(k2, (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(k3, (b, hkv, s, d), jnp.float32)
    lens = jnp.array([s - i * 7 for i in range(b)], jnp.int32)
    ref = decode_attention(q, k, v, lengths=lens, backend="xla")
    pal = decode_attention(q, k, v, lengths=lens, backend="pallas",
                           block_k=128)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=2e-5)


def test_decode_attention_respects_lengths():
    """Tokens beyond `length` must not affect the output."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(k1, (1, 2, 16), jnp.float32)
    k = jax.random.normal(k2, (1, 1, 64, 16), jnp.float32)
    v = jax.random.normal(k3, (1, 1, 64, 16), jnp.float32)
    lens = jnp.array([40], jnp.int32)
    base = decode_attention(q, k, v, lengths=lens, backend="pallas",
                            block_k=32)
    k2b = k.at[:, :, 50:].set(99.0)
    v2b = v.at[:, :, 50:].set(-99.0)
    pert = decode_attention(q, k2b, v2b, lengths=lens, backend="pallas",
                            block_k=32)
    np.testing.assert_allclose(np.asarray(base), np.asarray(pert), atol=1e-6)


# ------------------------------------------------------------------ gbdt infer
@pytest.fixture(scope="module")
def trained_gbdt():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(3000, 22)).astype(np.float32)
    y = ((X[:, 0] * X[:, 3] + X[:, 7] > 0)).astype(np.int32)
    return train_gbdt(X, y, n_trees=80, depth=5), X, y


def test_gbdt_kernel_matches_numpy(trained_gbdt):
    model, X, _ = trained_gbdt
    packed = pack_gbdt(model)
    ref = model.predict_proba(X[:300])
    for backend in ("jnp", "pallas"):
        got = gbdt_predict_proba(packed, X[:300], backend=backend)
        np.testing.assert_allclose(got, ref, atol=2e-6)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 200), seed=st.integers(0, 50))
def test_gbdt_kernel_any_batch(trained_gbdt, n, seed):
    model, _, _ = trained_gbdt
    packed = pack_gbdt(model)
    X = np.random.default_rng(seed).normal(size=(n, 22)).astype(np.float32)
    ref = model.predict_proba(X)
    got = gbdt_predict_proba(packed, X, backend="pallas")
    np.testing.assert_allclose(got, ref, atol=2e-6)


def test_gbdt_scorer_api(trained_gbdt):
    model, X, _ = trained_gbdt
    scorer = PallasGBDTScorer(model)
    got = scorer.predict_proba(X[:63])
    np.testing.assert_allclose(got, model.predict_proba(X[:63]), atol=2e-6)


def test_gbdt_learns(trained_gbdt):
    model, X, y = trained_gbdt
    acc = (model.predict(X) == y).mean()
    assert acc > 0.9


# ------------------------------------------- grid scorer, device-built rows
def _host_cross_product(scorer, H, backend):
    """The scorer's kernel rows built on the host, scored through
    ``gbdt_predict_proba``: the path the device-built rows replace."""
    n, c = H.shape[0], scorer.n_candidates
    X = np.concatenate([np.repeat(H, c, axis=0),
                        np.tile(scorer.theta, (n, 1))], axis=1)
    return np.asarray(gbdt_predict_proba(scorer.packed, X, backend=backend),
                      dtype=np.float64).reshape(n, c)


@pytest.fixture(scope="module")
def grid_theta():
    from repro.core import default_spaces
    return default_spaces().theta_features()


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("n", [3, 127, 128, 129, 700, 1025])
@pytest.mark.parametrize("op", ["read", "write"])
def test_grid_scorer_device_rows_bit_identical(tiny_models, grid_theta, op,
                                               n, x64):
    """Backend "pallas" builds the cross product on the device at a
    bucketed client count and returns exactly the probabilities of the
    host cross product, unpadded; ``calls`` counts the problem's rows."""
    scorer = GridGBDTScorer(tiny_models[op], grid_theta, backend="pallas")
    H = np.random.default_rng(n).normal(size=(n, scorer.n_h)).astype(
        np.float32)
    with jax.enable_x64(x64):
        got = scorer(H)
        want = _host_cross_product(scorer, H, "pallas")
    assert got.dtype == np.float64 and got.shape == (n, len(grid_theta))
    assert np.array_equal(got, want)
    assert dict(scorer.calls) == {("pallas", n * len(grid_theta)): 1}


@pytest.mark.parametrize("n", [1, 2])
def test_grid_scorer_small_batches_stay_jnp(tiny_models, grid_theta,
                                            monkeypatch, n):
    """Below ``_PALLAS_MIN_ROWS`` kernel rows, "auto" on a TPU still runs
    the jnp oracle over the host cross product, unchanged."""
    from repro.kernels.gbdt_infer import ops
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    scorer = GridGBDTScorer(tiny_models["read"], grid_theta, backend="auto")
    H = np.random.default_rng(n).normal(size=(n, scorer.n_h)).astype(
        np.float32)
    got = scorer(H)
    assert dict(scorer.calls) == {("jnp", n * len(grid_theta)): 1}
    assert np.array_equal(got, _host_cross_product(scorer, H, "jnp"))
    assert scorer._dev is None


def test_bucket_clients_rule():
    """Every bucket holds its count, fills whole 128-row kernel blocks,
    wastes under 1/8 from 1,024 clients up, and an octave of bucket sizes
    holds at most 8."""
    per_octave = {}
    for n in range(1, 1 << 17):
        b = bucket_clients(n)
        assert b >= n and b % 128 == 0, n
        if n >= 1024:
            assert b - n < n / 8, n
        per_octave.setdefault(b.bit_length(), set()).add(b)
    assert max(len(v) for v in per_octave.values()) <= 8
    assert bucket_clients(4704) == 5120


@pytest.mark.parametrize("lo,hi", [(3213, 3508), (5900, 6195)])
def test_grid_scorer_split_sweep_meets_two_buckets(tiny_models, grid_theta,
                                                   lo, hi):
    """The Fig 7 cell's decided splits move by tens of clients between
    intervals; over each op's whole range the kernel meets at most two
    client buckets (``gbdt.new_shape``), and ``gbdt.pad_rows`` counts the
    padding. One candidate keeps the interpreted kernel short."""
    from repro.core.runtime.telemetry import recorder
    scorer = GridGBDTScorer(tiny_models["write"], grid_theta[:1],
                            backend="pallas")
    H = np.random.default_rng(lo).normal(size=(hi, scorer.n_h)).astype(
        np.float32)
    with recorder.enabled() as rec:
        for n in range(lo, hi + 1):
            scorer(H[:n])
        counters = rec.snapshot()["counters"]
    assert 1 <= counters["gbdt.new_shape"] <= 2
    assert counters["gbdt.pad_rows"] == sum(
        bucket_clients(n) - n for n in range(lo, hi + 1))
    assert sum(scorer.calls.values()) == hi - lo + 1


def test_grid_scorer_pickles_without_device_arrays(tiny_models, grid_theta):
    """Device copies made by a kernel call are not pickled: the unpickled
    scorer holds no device array and scores the same."""
    import pickle
    scorer = GridGBDTScorer(tiny_models["read"], grid_theta, backend="pallas")
    H = np.random.default_rng(7).normal(size=(200, scorer.n_h)).astype(
        np.float32)
    want = scorer(H)
    assert scorer._dev is not None
    clone = pickle.loads(pickle.dumps(scorer))
    assert clone._dev is None and not clone._buckets
    assert not any(isinstance(v, jax.Array) for v in vars(clone).values())
    assert np.array_equal(clone(H), want)
