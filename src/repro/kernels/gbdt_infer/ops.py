"""Public op: batched GBDT probability scoring with backend switch.

``pack_gbdt`` converts a trained :class:`ObliviousGBDT` into the padded,
TPU-tile-aligned tensors the Pallas kernel consumes. ``gbdt_predict_proba``
scores a candidate batch; backend "pallas" runs the kernel (compiled on a
TPU, interpreted on the CPU — :func:`default_interpret` decides), backend
"jnp" runs the oracle, backend "numpy" uses the model's native numpy path
(fastest on a CPU host — used by the online controller loop there), and
backend "auto" picks per call from the accelerator platform and the batch
size.

The packed tensors stay NumPy and move to the device only inside a call,
so building or pickling a scorer (and the policy that holds it) touches
no device — a worker process that unpickles a policy never reaches for a
chip. :class:`GridGBDTScorer` keeps device copies of its grid and tables
after its first kernel call, and drops them when pickled.

:class:`GridGBDTScorer` is the fleet-tuning entry point: it scores a
whole node's clients against the static candidate grid in one call,
factorizing the split comparisons so the per-client cost falls with
batch size (see the class docstring). On the kernel path it uploads only
the client rows, padded to a bucketed count (:func:`bucket_clients`),
and builds the client × candidate rows on the device.
"""
from __future__ import annotations

import functools
from collections import Counter
from dataclasses import dataclass
from typing import Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ml.gbdt import ObliviousGBDT, _sigmoid
from repro.core.runtime.telemetry.recorder import active as _telemetry
from repro.kernels.gbdt_infer.kernel import gbdt_logits_pallas
from repro.kernels.gbdt_infer.ref import gbdt_logits_ref

Backend = Literal["pallas", "jnp", "numpy", "auto"]

# below this many rows a TPU kernel launch is not worth it; the jnp oracle
# (one fused XLA program) wins
_PALLAS_MIN_ROWS = 128


def resolve_backend(backend: Backend, n_rows: int) -> str:
    """Map "auto" to a concrete backend for an ``n_rows``-row batch.

    On CPU the model's native numpy path is fastest at every batch size we
    deploy (the Pallas kernel only runs interpreted there); on TPU the
    kernel pays off once the batch fills a block, with the jnp oracle
    covering small probes.
    """
    if backend != "auto":
        return backend
    if jax.default_backend() == "tpu":
        return "pallas" if n_rows >= _PALLAS_MIN_ROWS else "jnp"
    return "numpy"


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def bucket_clients(n: int) -> int:
    """Client count a kernel batch of ``n`` clients is padded to.

    ``n`` rounded up to a multiple of ``max(128, 2**(floor(log2 n) - 3))``:
    under 1/8 padding from 1,024 clients up, at most 8 counts per octave,
    and every count a multiple of 128, so its rows fill whole kernel
    blocks whatever the grid's size. The kernel then compiles once per
    bucket, not once per decided split.
    """
    n = max(int(n), 1)
    return _round_up(n, max(128, 1 << max(n.bit_length() - 4, 0)))


@functools.partial(jax.jit, static_argnames=("f_pad",))
def _cross_rows(h, theta, *, f_pad: int):
    """Kernel rows of the client × candidate product, built on the device:
    row ``i * c + j`` is ``[h[i] | theta[j]]``, zero-padded to ``f_pad``
    columns.

    Each row is gathered from the column-padded halves and merged by a
    select, so every value is copied, never computed. (``h`` broadcast
    and reshaped to rows is the same program, but XLA:TPU's relayout of
    it compiles for about a minute at 5,120 clients.)
    """
    (n, n_h), c = h.shape, theta.shape[0]
    h_p = jnp.pad(h, ((0, 0), (0, f_pad - n_h)))
    t_p = jnp.pad(theta, ((0, 0), (n_h, f_pad - n_h - theta.shape[1])))
    r = jnp.arange(n * c, dtype=jnp.int32)
    return jnp.where(jnp.arange(f_pad) < n_h,
                     jnp.take(h_p, r // c, axis=0, mode="clip"),
                     jnp.take(t_p, r % c, axis=0, mode="clip"))


@dataclass(frozen=True)
class PackedGBDT:
    """Kernel tensors (NumPy; see the module docstring)."""
    sel: np.ndarray       # (F_pad, D * T_pad) one-hot selector, level-major
    thr: np.ndarray       # (1, D * T_pad) thresholds, level-major
    leaf_t: np.ndarray    # (2**D, T_pad) transposed leaf table
    base: np.float32
    model: ObliviousGBDT  # source model (backends "numpy" and "jnp")

    @property
    def f_pad(self) -> int:
        return self.sel.shape[0]

    @property
    def t_pad(self) -> int:
        return self.leaf_t.shape[1]


def pack_gbdt(model: ObliviousGBDT) -> PackedGBDT:
    feat, thr, leaf, base = model.packed()
    t, d = feat.shape
    t_pad = _round_up(max(t, 1), 128)          # trees fill whole lane tiles
    f_pad = _round_up(model.n_features, 8)
    # padded trees: all-false splits (threshold +inf) and zero leaves;
    # column l * t_pad + k holds level l of tree k, so each level is one
    # lane-aligned (BN, t_pad) slice of the kernel's split values
    thr_p = np.full((d, t_pad), np.float32(np.inf))
    thr_p[:, :t] = thr.T
    sel = np.zeros((f_pad, d, t_pad), dtype=np.float32)
    sel[feat.T, np.arange(d)[:, None], np.arange(t)[None, :]] = 1.0
    leaf_t = np.zeros((leaf.shape[1], t_pad), dtype=np.float32)
    leaf_t[:, :t] = leaf.T
    return PackedGBDT(sel=sel.reshape(f_pad, d * t_pad),
                      thr=thr_p.reshape(1, d * t_pad), leaf_t=leaf_t,
                      base=np.float32(base[0]), model=model)


def gbdt_predict_proba(
    packed: PackedGBDT,
    X: np.ndarray,
    backend: Backend = "pallas",
    block_n: int = 128,
) -> np.ndarray:
    X = np.asarray(X, dtype=np.float32)
    n, f = X.shape
    m = packed.model
    if f != m.n_features:
        raise ValueError(f"feature dim {f} != model {m.n_features}")
    backend = resolve_backend(backend, n)
    if backend == "numpy":
        return m.predict_proba(X)
    if backend == "pallas":
        n_pad = _round_up(max(n, 1), block_n)
        Xp = np.zeros((n_pad, packed.f_pad), dtype=np.float32)
        Xp[:n, :f] = X
        logits = gbdt_logits_pallas(
            jnp.asarray(Xp), packed.sel, packed.thr, packed.leaf_t,
            packed.base, block_n=block_n)
    elif backend == "jnp":
        feat, thr, leaf, base = m.packed()
        logits = gbdt_logits_ref(jnp.asarray(X), feat, thr, leaf, base[0])
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return np.asarray(jax.nn.sigmoid(logits)[:n])


class PallasGBDTScorer:
    """predict_proba adapter: CARAT controller -> Pallas GBDT kernel.

    On TPU this is the deployed inference path (whole candidate space in one
    kernel launch per probe); on CPU the kernel runs interpreted, so the
    online benchmarks default to the model's native numpy path and the
    kernel is exercised by the correctness suite instead.
    """

    def __init__(self, model: ObliviousGBDT, backend: Backend = "pallas",
                 block_n: int = 128):
        self.packed = pack_gbdt(model)
        self.backend = backend
        self.block_n = block_n

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        return gbdt_predict_proba(self.packed, X, backend=self.backend,
                                  block_n=self.block_n)


class GridGBDTScorer:
    """Multi-client batched scorer over a *static* candidate grid.

    Scores ``H`` (n_clients, F_h) snapshot-feature rows against every row of
    a fixed ``theta`` (n_cand, F_t) candidate grid in one call, returning
    (n_clients, n_cand) probabilities — the fleet-tuning hot path.

    The model's features are the concatenation [H | theta], so every
    oblivious split tests either a client feature or a candidate feature.
    Because the grid is static, the candidate half of every split is
    evaluated once at construction; per call only the client half runs:
    O((n_clients + n_cand) * T * D) comparisons instead of
    O(n_clients * n_cand * T * D) for the naive cross-product, followed by
    one flat leaf gather. The two halves combine by integer addition since
    each tree level owns a disjoint bit of the leaf index.

    Backend "numpy" is **bit-identical** to calling
    ``ObliviousGBDT.predict_proba`` on the equivalent cross-product rows:
    the comparisons see the same float32 values and the leaf gather + sum
    replicate ``decision_function``'s flat-take accumulation order exactly.
    That is what lets the fleet controller prove its decisions equal the
    per-client path. Backends "jnp"/"pallas" go through the packed kernel
    tensors (float32-tolerance agreement, used on accelerators).

    ``calls`` counts scored batches by ``(resolved backend, kernel rows)``
    — what "auto" chose for each batch size; the rows are the problem's,
    ``n_clients * n_cand``, without padding.

    Backend "pallas" uploads only ``H``, zero-padded to
    :func:`bucket_clients` rows, builds the cross-product rows on the
    device (:func:`_cross_rows`: the same float32 values in the same
    order as the host cross product), runs the kernel and the sigmoid
    there, and pulls one float32 probability per row. The grid and the
    kernel tables go to the device on the first such call. While a
    telemetry recorder is on it counts ``gbdt.pad_rows`` (kernel rows
    added by the bucketing) and ``gbdt.new_shape`` (calls at a bucket
    this scorer had not dispatched before: the kernel's compiles).
    """

    def __init__(self, model: ObliviousGBDT, theta: np.ndarray,
                 backend: Backend = "auto", block_n: int = 128,
                 cand_chunk: int = 8):
        self.model = model
        self.theta = np.asarray(theta, dtype=np.float32)
        if self.theta.ndim != 2:
            raise ValueError("theta must be (n_candidates, n_theta_features)")
        self.backend = backend
        self.block_n = block_n
        self.cand_chunk = max(int(cand_chunk), 1)
        self.calls: Counter = Counter()
        self._buffers: dict = {}       # (n, chunk) -> (int32 idx, f32 gather)
        self._dev: Optional[tuple] = None   # device grid + kernel tables
        self._buckets: set = set()          # client buckets dispatched
        self.packed = pack_gbdt(model)
        n_h = model.n_features - self.theta.shape[1]
        if n_h <= 0:
            raise ValueError(
                f"model consumes {model.n_features} features but the grid "
                f"supplies {self.theta.shape[1]}; no client features left")
        self.n_h = n_h
        feat = model.feat.reshape(-1).astype(np.int64)
        self._thr = model.thr.reshape(-1)
        self._is_theta = feat >= n_h
        self._client_ix = np.where(self._is_theta, 0, feat)
        # int32 index math throughout: flat leaf offsets max out at
        # T * 2**D (a few thousand), and halving the (n, n_cand, T) index
        # footprint keeps the hot batch inside cache. Gathered values —
        # hence bit-identity — do not depend on the index dtype.
        self._weights = (1 << np.arange(model.depth - 1, -1, -1)).astype(np.int32)
        # candidate half, evaluated once: per-(tree,level) bits -> per-tree
        # partial leaf index, pre-offset into the flat leaf table
        g_t = self.theta[:, np.where(self._is_theta, feat - n_h, 0)]
        bits_t = ((g_t > self._thr) & self._is_theta).astype(np.int32)
        idx_t = (bits_t.reshape(-1, model.n_trees, model.depth)
                 * self._weights).sum(axis=2, dtype=np.int32)
        tree_base = np.arange(model.n_trees, dtype=np.int32) << np.int32(model.depth)
        self._idx_theta_flat = idx_t + tree_base          # (n_cand, T)
        self._leaf_flat = model.leaf.ravel()

    def __getstate__(self) -> dict:
        # device copies stay in their process; an unpickled scorer
        # uploads (and compiles) afresh
        return {**self.__dict__, "_dev": None, "_buckets": set()}

    @property
    def n_candidates(self) -> int:
        return self.theta.shape[0]

    def __call__(self, H: np.ndarray,
                 backend: Optional[Backend] = None) -> np.ndarray:
        H = np.asarray(H, dtype=np.float32)
        if H.ndim == 1:
            H = H[None, :]
        if H.shape[1] != self.n_h:
            raise ValueError(f"client feature dim {H.shape[1]} != {self.n_h}")
        rows = H.shape[0] * self.n_candidates
        be = resolve_backend(backend or self.backend, rows)
        self.calls[(be, rows)] += 1
        if be == "numpy":
            return self._predict_numpy(H)
        if be == "pallas":
            return self._predict_pallas(H)
        return self._predict_packed(H, be)

    # ------------------------------------------------------------ backends
    def _predict_numpy(self, H: np.ndarray) -> np.ndarray:
        m = self.model
        g_c = H[:, self._client_ix]
        bits_c = ((g_c > self._thr) & ~self._is_theta).astype(np.int32)
        idx_c = (bits_c.reshape(-1, m.n_trees, m.depth)
                 * self._weights).sum(axis=2, dtype=np.int32)   # (n, T)
        # Chunk the candidate axis so the (n, chunk, T) index + gather
        # working set stays cache-resident, and reuse the chunk buffers
        # across calls (the fleet scores every probe interval). Each output
        # element is still an unbroken C-contiguous row sum over trees, so
        # neither chunking nor buffering changes a value.
        n, c = idx_c.shape[0], self.n_candidates
        t = m.n_trees
        logits = np.empty((n, c), dtype=np.float32)
        for k0 in range(0, c, self.cand_chunk):
            k1 = min(k0 + self.cand_chunk, c)
            key = (n, k1 - k0)
            if key not in self._buffers:
                if len(self._buffers) > 64:      # bound fleet-size churn
                    self._buffers.clear()
                self._buffers[key] = (
                    np.empty((n, k1 - k0, t), dtype=np.int32),
                    np.empty((n, k1 - k0, t), dtype=np.float32))
            flat, gathered = self._buffers[key]
            np.add(idx_c[:, None, :], self._idx_theta_flat[None, k0:k1, :],
                   out=flat)
            self._leaf_flat.take(flat, out=gathered)
            gathered.sum(axis=-1, out=logits[:, k0:k1])
        return _sigmoid(m.base + logits)

    def _predict_packed(self, H: np.ndarray, backend: str) -> np.ndarray:
        n, c = H.shape[0], self.n_candidates
        X = np.concatenate([np.repeat(H, c, axis=0),
                            np.tile(self.theta, (n, 1))], axis=1)
        probs = gbdt_predict_proba(self.packed, X, backend=backend,
                                   block_n=self.block_n)
        return np.asarray(probs, dtype=np.float64).reshape(n, c)

    def _predict_pallas(self, H: np.ndarray) -> np.ndarray:
        n, c = H.shape[0], self.n_candidates
        if self._dev is None:
            p = self.packed
            self._dev = tuple(jnp.asarray(a) for a in
                              (self.theta, p.sel, p.thr, p.leaf_t, p.base))
        theta, sel, thr, leaf_t, base = self._dev
        # rounding to block_n is a no-op at the default 128: n_pad * c
        # rows fill whole kernel blocks
        n_pad = _round_up(bucket_clients(n), self.block_n)
        H_pad = np.zeros((n_pad, self.n_h), dtype=np.float32)
        H_pad[:n] = H
        x = _cross_rows(H_pad, theta, f_pad=self.packed.f_pad)
        logits = gbdt_logits_pallas(x, sel, thr, leaf_t, base,
                                    block_n=self.block_n)
        probs = np.asarray(jax.nn.sigmoid(logits))[:n * c]
        rec = _telemetry()
        if rec.enabled:
            rec.count("gbdt.pad_rows", x.shape[0] - n * c)
            if n_pad not in self._buckets:
                rec.count("gbdt.new_shape")
        self._buckets.add(n_pad)
        return probs.astype(np.float64).reshape(n, c)
