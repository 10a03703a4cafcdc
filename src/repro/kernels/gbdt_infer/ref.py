"""Pure-jnp oracle for oblivious-GBDT ensemble inference.

Straight from the model's own layout, independent of the kernel's
packing: gather each (tree, level) split feature, compare against its
threshold, bit-pack the levels into a leaf index (level 0 is the MSB, as
in ``ObliviousGBDT.decision_function``), gather the leaf values and sum
over trees. The gather is an integer gather, exact on every backend.
"""
from __future__ import annotations

import jax.numpy as jnp


def gbdt_logits_ref(
    x: jnp.ndarray,       # (N, F) float32
    feat: jnp.ndarray,    # (T, D) int split feature per tree level
    thr: jnp.ndarray,     # (T, D) float32 split thresholds
    leaf: jnp.ndarray,    # (T, 2**D) float32 leaf values
    base,                 # scalar initial log-odds
) -> jnp.ndarray:         # (N,)
    d = feat.shape[1]
    bits = (x[:, feat] > thr[None]).astype(jnp.int32)        # (N, T, D)
    weights = jnp.left_shift(1, jnp.arange(d - 1, -1, -1, dtype=jnp.int32))
    idx = (bits * weights).sum(axis=2)                       # (N, T)
    contrib = jnp.take_along_axis(leaf[None], idx[..., None], axis=2)[..., 0]
    return base + contrib.sum(axis=1)
