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
chip.

:class:`GridGBDTScorer` is the fleet-tuning entry point: it scores a
whole node's clients against the static candidate grid in one call,
factorizing the split comparisons so the per-client cost falls with
batch size (see the class docstring).
"""
from __future__ import annotations

from collections import Counter
from dataclasses import dataclass
from typing import Literal, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ml.gbdt import ObliviousGBDT, _sigmoid
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
    — what "auto" chose for each batch size.
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

