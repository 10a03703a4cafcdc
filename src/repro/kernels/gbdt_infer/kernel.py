"""Pallas TPU kernel: oblivious-GBDT ensemble inference.

CARAT's hot loop scores every candidate configuration against the current
snapshot every probe interval on every host. The ensemble is tiny (a few
hundred trees x depth 5) but latency matters (Table VIII) and the batch is
the whole candidate space, so the kernel keeps the entire model resident in
VMEM and streams row blocks through it:

* feature gather  -> one one-hot matmul on the MXU against a level-major
  selector, so each level's split values are a lane-aligned (BN, T) slice;
* level compares  -> VPU;
* leaf selection  -> a branch-free select tree over the transposed
  (2**D, T) leaf table: the deepest level picks between sibling leaves,
  each shallower level between the surviving pairs (2**D - 1 selects);
* tree sum        -> transpose + sublane reduce into a lane-dense (1, BN)
  output row.

Every in-kernel op is one Mosaic lowers; ``tests/test_tpu_compile.py``
compiles the kernel for a described v5e chip at production shapes. The
gather matmul runs at ``Precision.HIGHEST`` — a one-hot selector times
an f32 feature is exact only at full f32 precision, and a rounded split
value flips comparisons near a threshold.

VMEM at the production shapes (T_pad=512, D=5, F_pad=24, BN=128):
  x tile      128 x 24 x 4       =  12 KiB
  sel         24 x 2560 x 4      = 240 KiB
  thr         2560 x 4           =  10 KiB
  leaf_t      32 x 512 x 4       =  64 KiB
  split vals  128 x 2560 x 4     = 1.25 MiB
well inside the scoped VMEM of a v5e core.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def default_interpret() -> bool:
    """Whether Pallas kernels run interpreted: on the CPU backend (where
    the interpreter is Pallas's only lowering), never on a TPU."""
    return jax.default_backend() == "cpu"


def _gbdt_kernel(x_ref, sel_ref, thr_ref, leaf_t_ref, out_ref, *,
                 depth: int, t_pad: int):
    # (BN, D*T_pad): split value of every (level, tree), level-major
    g = jnp.dot(x_ref[...], sel_ref[...],
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)
    bits = g > thr_ref[...]
    leaf_t = leaf_t_ref[...]                       # (2**D, T_pad)
    # leaf index bit-packs the levels with level 0 as the MSB (ref.py),
    # so the deepest level chooses between adjacent leaves
    vals = [leaf_t[j:j + 1, :] for j in range(1 << depth)]
    for level in reversed(range(depth)):
        bit = bits[:, level * t_pad:(level + 1) * t_pad]
        vals = [jnp.where(bit, vals[2 * j + 1], vals[2 * j])
                for j in range(len(vals) // 2)]
    # (BN, T_pad) per-tree leaf values -> lane-dense (1, BN) row sums
    out_ref[...] = jnp.sum(vals[0].T, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def gbdt_logits_pallas(
    x: jnp.ndarray,       # (N, F_pad) float32, N % block_n == 0
    sel: jnp.ndarray,     # (F_pad, D*T_pad) float32 one-hot, level-major
    thr: jnp.ndarray,     # (1, D*T_pad) float32, level-major
    leaf_t: jnp.ndarray,  # (2**D, T_pad) float32, T_pad % 128 == 0
    base: jnp.ndarray,    # () float32
    *,
    block_n: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(N,) logits. ``interpret=None`` chooses from the platform
    (:func:`default_interpret`)."""
    if interpret is None:
        interpret = default_interpret()
    n, f = x.shape
    n_leaves, t_pad = leaf_t.shape
    depth = n_leaves.bit_length() - 1
    assert n % block_n == 0 and t_pad % 128 == 0
    assert sel.shape == (f, depth * t_pad) and thr.shape == (1, depth * t_pad)
    # int32 block indices: under jax_enable_x64 a bare 0 is an i64 that
    # Mosaic cannot return from an index map
    def resident(i):
        return jnp.int32(0), jnp.int32(0)

    out = pl.pallas_call(
        functools.partial(_gbdt_kernel, depth=depth, t_pad=t_pad),
        grid=(n // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, f), lambda i: (i, jnp.int32(0))),
            pl.BlockSpec(sel.shape, resident),
            pl.BlockSpec(thr.shape, resident),
            pl.BlockSpec(leaf_t.shape, resident),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (jnp.int32(0), i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(x, sel, thr, leaf_t)
    return base + out[0]
