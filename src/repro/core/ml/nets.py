"""Neural baselines (paper Table IV): FC-NN, vanilla RNN, TCN — in JAX.

The paper feeds flattened history to the FC-NN and per-timestep vectors to
the RNN/TCN. Our feature layout is [metrics_t (6), metrics_{t-1} (6),
config (2)] + candidate theta (2); sequence models receive the two metric
timesteps as a length-2 sequence with the static (config, theta) features
appended to every step. Training: Adam + BCE, mini-batches, early stop.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

METRICS_PER_STEP = 6
N_STEPS = 2                 # history k=1 => [s_{t-1}, s_t]
STATIC_DIM = 10             # deltas (6) + current config (2) + theta (2)


def _split_sequence(X: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(n, 22) -> sequence (n, 2, 6) ordered [t-1, t], static (n, 10)."""
    cur = X[:, 0:METRICS_PER_STEP]
    prev = X[:, METRICS_PER_STEP:2 * METRICS_PER_STEP]
    seq = jnp.stack([prev, cur], axis=1)
    static = X[:, 2 * METRICS_PER_STEP:]
    return seq, static


def _dense_init(rng, n_in, n_out):
    k1, _ = jax.random.split(rng)
    scale = jnp.sqrt(2.0 / n_in)
    return {"w": jax.random.normal(k1, (n_in, n_out), jnp.float32) * scale,
            "b": jnp.zeros((n_out,), jnp.float32)}


def _dense(p, x):
    return x @ p["w"] + p["b"]


# ----------------------------------------------------------------------------
@dataclass
class NetModel:
    """A trained JAX net with a numpy-facing predict_proba."""
    params: Dict
    apply_fn: Callable
    mu: np.ndarray
    sigma: np.ndarray
    name: str = "net"

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        Z = (np.asarray(X, np.float32) - self.mu) / self.sigma
        logits = self._jitted(self.params, jnp.asarray(Z))
        return np.asarray(jax.nn.sigmoid(logits))

    def predict(self, X: np.ndarray) -> np.ndarray:
        return (self.predict_proba(X) >= 0.5).astype(np.int32)

    @functools.cached_property
    def _jitted(self):
        return jax.jit(self.apply_fn)


# --- FC-NN --------------------------------------------------------------------
class FCNN:
    name = "fcnn"

    def __init__(self, in_dim: int, hidden: Tuple[int, ...] = (64, 64)):
        self.in_dim = in_dim
        self.hidden = hidden

    def init(self, rng) -> Dict:
        dims = (self.in_dim,) + self.hidden + (1,)
        keys = jax.random.split(rng, len(dims) - 1)
        return {f"l{i}": _dense_init(k, dims[i], dims[i + 1])
                for i, k in enumerate(keys)}

    def apply(self, params, X):
        h = X
        n = len(self.hidden)
        for i in range(n):
            h = jax.nn.relu(_dense(params[f"l{i}"], h))
        return _dense(params[f"l{n}"], h)[:, 0]


# --- vanilla RNN ---------------------------------------------------------------
class VanillaRNN:
    name = "rnn"

    def __init__(self, in_dim: int, hidden: int = 32):
        self.in_dim = in_dim           # full flattened dim (for API parity)
        self.hidden = hidden
        self.step_dim = METRICS_PER_STEP + STATIC_DIM

    def init(self, rng) -> Dict:
        k1, k2, k3, k4 = jax.random.split(rng, 4)
        return {
            "wx": _dense_init(k1, self.step_dim, self.hidden),
            "wh": _dense_init(k2, self.hidden, self.hidden),
            "head": _dense_init(k3, self.hidden, self.hidden),
            "out": _dense_init(k4, self.hidden, 1),
        }

    def apply(self, params, X):
        seq, static = _split_sequence(X)
        n = X.shape[0]
        h = jnp.zeros((n, self.hidden), jnp.float32)

        def cell(h, x_t):
            h2 = jnp.tanh(_dense(params["wx"], x_t) + _dense(params["wh"], h))
            return h2, None

        xs = jnp.concatenate(
            [seq, jnp.broadcast_to(static[:, None, :],
                                   (n, N_STEPS, STATIC_DIM))], axis=-1)
        h, _ = jax.lax.scan(cell, h, jnp.swapaxes(xs, 0, 1))
        h = jax.nn.relu(_dense(params["head"], h))    # nonlinear readout
        return _dense(params["out"], h)[:, 0]


# --- TCN ------------------------------------------------------------------------
class TCN:
    name = "tcn"

    def __init__(self, in_dim: int, channels: int = 32, kernel: int = 2):
        self.in_dim = in_dim
        self.channels = channels
        self.kernel = kernel
        self.step_dim = METRICS_PER_STEP + STATIC_DIM

    def init(self, rng) -> Dict:
        k1, k2, k3 = jax.random.split(rng, 3)
        c = self.channels
        return {
            "conv1": {"w": jax.random.normal(
                k1, (self.kernel, self.step_dim, c), jnp.float32)
                      * jnp.sqrt(2.0 / (self.kernel * self.step_dim)),
                      "b": jnp.zeros((c,), jnp.float32)},
            "conv2": {"w": jax.random.normal(
                k2, (self.kernel, c, c), jnp.float32)
                      * jnp.sqrt(2.0 / (self.kernel * c)),
                      "b": jnp.zeros((c,), jnp.float32)},
            "out": _dense_init(k3, c, 1),
        }

    @staticmethod
    def _causal_conv(p, x, kernel):
        # x: (n, t, c_in); left-pad for causality
        pad = [(0, 0), (kernel - 1, 0), (0, 0)]
        xp = jnp.pad(x, pad)
        return jax.lax.conv_general_dilated(
            xp, p["w"], window_strides=(1,), padding="VALID",
            dimension_numbers=("NWC", "WIO", "NWC")) + p["b"]

    def apply(self, params, X):
        seq, static = _split_sequence(X)
        n = X.shape[0]
        xs = jnp.concatenate(
            [seq, jnp.broadcast_to(static[:, None, :],
                                   (n, N_STEPS, STATIC_DIM))], axis=-1)
        h = jax.nn.relu(self._causal_conv(params["conv1"], xs, self.kernel))
        h = jax.nn.relu(self._causal_conv(params["conv2"], h, self.kernel))
        return _dense(params["out"], h[:, -1, :])[:, 0]


# --- shared trainer -------------------------------------------------------------
def train_net(
    arch,
    X: np.ndarray,
    y: np.ndarray,
    X_val=None,
    y_val=None,
    epochs: int = 60,
    batch: int = 512,
    lr: float = 1e-3,
    weight_decay: float = 1e-4,
    seed: int = 0,
    patience: int = 25,
) -> NetModel:
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    mu = X.mean(axis=0)
    sigma = X.std(axis=0) + 1e-6
    Z = jnp.asarray((X - mu) / sigma)
    Y = jnp.asarray(y)

    rng = jax.random.PRNGKey(seed)
    params = arch.init(rng)

    def loss_fn(p, xb, yb):
        logits = arch.apply(p, xb)
        return jnp.mean(
            jnp.maximum(logits, 0) - logits * yb
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    # hand-rolled Adam (no optax in this container)
    def adam_init(p):
        z = jax.tree_util.tree_map(jnp.zeros_like, p)
        return {"m": z, "v": jax.tree_util.tree_map(jnp.zeros_like, p),
                "t": jnp.zeros((), jnp.int32)}

    @jax.jit
    def update(p, opt, xb, yb):
        g = jax.grad(loss_fn)(p, xb, yb)
        t = opt["t"] + 1
        m = jax.tree_util.tree_map(lambda m, g: 0.9 * m + 0.1 * g, opt["m"], g)
        v = jax.tree_util.tree_map(lambda v, g: 0.999 * v + 0.001 * g * g,
                                   opt["v"], g)
        # float32 bias corrections: a Python float to a traced int32 power
        # is float64 under x64, which would promote the parameters
        b1 = 1 - jnp.float32(0.9) ** t
        b2 = 1 - jnp.float32(0.999) ** t
        mh = jax.tree_util.tree_map(lambda m: m / b1, m)
        vh = jax.tree_util.tree_map(lambda v: v / b2, v)
        p2 = jax.tree_util.tree_map(
            lambda p, mh, vh: p - lr * (mh / (jnp.sqrt(vh) + 1e-8)
                                        + weight_decay * p), p, mh, vh)
        return p2, {"m": m, "v": v, "t": t}

    opt = adam_init(params)
    nprng = np.random.Generator(np.random.PCG64(seed))
    n = len(X)
    best_params, best_err, since = params, np.inf, 0
    has_val = X_val is not None
    if has_val:
        Zv = jnp.asarray((np.asarray(X_val, np.float32) - mu) / sigma)
        Yv = np.asarray(y_val)

    for ep in range(epochs):
        order = nprng.permutation(n)
        for s in range(0, n, batch):
            idx = order[s:s + batch]
            params, opt = update(params, opt, Z[idx], Y[idx])
        if has_val:
            logits = arch.apply(params, Zv)
            pred = (np.asarray(logits) >= 0).astype(np.int32)
            err = float(np.mean(pred != Yv))
            if err < best_err - 1e-4:
                best_err, best_params, since = err, params, 0
            else:
                since += 1
                if since >= patience:
                    break
    return NetModel(params=best_params if has_val else params,
                    apply_fn=arch.apply, mu=mu, sigma=sigma, name=arch.name)
