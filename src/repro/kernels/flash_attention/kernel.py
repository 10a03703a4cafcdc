"""Pallas TPU kernel: flash attention (online softmax), GQA-aware.

Grid layout: (batch * q_heads, Sq / BQ, Sk / BK) with the key dimension
innermost so the (BQ, D) accumulator, running max and running sum live in
VMEM scratch across the k-sweep. BlockSpec index maps route each q-head to
its kv-head (grouped-query attention) without materializing repeated K/V.

Masking menu (static): causal, sliding-window (h2o-danube, recurrentgemma
local blocks), or bidirectional (HuBERT encoder). Fully-masked k-blocks are
skipped via ``pl.when`` on block indices, so the causal kernel does ~half
the work and the sliding-window kernel touches only O(window) keys per
query block — the TPU adaptation of the paper-agnostic GPU flash pattern
(no warp shuffles; the online-softmax carry lives in VMEM scratch, block
shapes are (8,128)-tile aligned for the MXU).

VMEM working set per grid cell (BQ=BK=512, D=128, fp32):
  q 256 KiB + k 256 KiB + v 256 KiB + acc 256 KiB + logits 1 MiB ~= 2 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
               *, scale: float, causal: bool, window: int,
               block_q: int, block_k: int, k_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level skip: with causal masking, k-blocks fully above the
    # diagonal contribute nothing; with a sliding window, k-blocks fully
    # behind the window contribute nothing either.
    q_start = qi * block_q
    k_start = kj * block_k
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window > 0:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[...].astype(jnp.float32)           # (BQ, D)
        k = k_ref[...].astype(jnp.float32)           # (BK, D)
        v = v_ref[...].astype(jnp.float32)           # (BK, D)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window > 0:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]                          # (BQ, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(logits, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)                  # (BQ, BK)
        l_new = l_prev * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(kj == k_blocks - 1)
    def _finish():
        l = l_ref[...]
        o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "block_q", "block_k",
                     "interpret"))
def flash_attention_pallas(
    q: jnp.ndarray,            # (B, Hq, Sq, D)
    k: jnp.ndarray,            # (B, Hkv, Sk, D)
    v: jnp.ndarray,            # (B, Hkv, Sk, D)
    causal: bool = True,
    window: int = 0,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, "GQA requires Hq % Hkv == 0"
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    scale_val = float(scale) if scale is not None else float(d) ** -0.5
    k_blocks = sk // block_k
    grid = (b * hq, sq // block_q, k_blocks)

    def q_map(h, i, j):
        return (h, i, 0)

    def kv_map(h, i, j):
        return (h // group, j, 0)     # GQA: q-head h reads kv-head h//group

    qr = q.reshape(b * hq, sq, d)
    kr = k.reshape(b * hkv, sk, d)
    vr = v.reshape(b * hkv, sk, d)

    out = pl.pallas_call(
        functools.partial(
            _fa_kernel, scale=scale_val, causal=causal, window=window,
            block_q=block_q, block_k=block_k, k_blocks=k_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), q_map),
            pl.BlockSpec((None, block_k, d), kv_map),
            pl.BlockSpec((None, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, hq, sq, d)
