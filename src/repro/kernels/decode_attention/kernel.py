"""Pallas TPU kernel: single-token decode attention over a long KV cache.

The decode hot loop is memory-bound: one query token streams the whole KV
cache from HBM. The kernel blocks over cache length with the online-softmax
carry in VMEM scratch, exactly like flash attention but with BQ = heads of
one kv-group stacked into the sublane dimension (a (G, D) tile instead of a
(1, D) sliver — G=Hq/Hkv query heads share each kv-head's cache block, so
the MXU sees a dense (G, BK) logits tile and K/V bytes are read once per
group rather than once per query head).

Grid: (B * Hkv, S / BK); the q BlockSpec delivers the (G, D) group tile.
Valid-length masking supports ragged batches (serving).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                *, scale: float, block_k: int, k_blocks: int, heads: int):
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[0]
    k_start = kj * block_k

    @pl.when(k_start < length)
    def _body():
        q = q_ref[...].astype(jnp.float32)            # (G, D)
        k = k_ref[...].astype(jnp.float32)            # (BK, D)
        v = v_ref[...].astype(jnp.float32)            # (BK, D)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, BK)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (heads, block_k), 1)
        logits = jnp.where(kpos < length, logits, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == k_blocks - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_k", "interpret"))
def decode_attention_pallas(
    q: jnp.ndarray,          # (B, Hq, D)
    k: jnp.ndarray,          # (B, Hkv, S, D)
    v: jnp.ndarray,          # (B, Hkv, S, D)
    lengths: jnp.ndarray,    # (B,) int32
    scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = True,
) -> jnp.ndarray:
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    block_k = min(block_k, s)
    assert s % block_k == 0
    scale_val = float(scale) if scale is not None else float(d) ** -0.5
    k_blocks = s // block_k
    grid = (b * hkv, k_blocks)

    qr = q.reshape(b, hkv, group, d).reshape(b * hkv, group, d)
    kr = k.reshape(b * hkv, s, d)
    vr = v.reshape(b * hkv, s, d)
    lens = jnp.repeat(lengths.astype(jnp.int32), hkv)

    out = pl.pallas_call(
        functools.partial(_dec_kernel, scale=scale_val, block_k=block_k,
                          k_blocks=k_blocks, heads=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda h, j: (h,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, group, d), lambda h, j: (h, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda h, j: (h, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda h, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, group, d), lambda h, j: (h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, group, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((group, d), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lens, qr, kr, vr)
    return out.reshape(b, hkv, group, d).reshape(b, hq, d)
