"""Pallas TPU flash-attention (prefill/train) kernel.

Grid: (batch, q_heads, q_blocks, k_blocks) with the k dimension innermost and
"arbitrary" (sequential) so VMEM scratch accumulators carry the online
softmax across k blocks.  GQA is handled in the BlockSpec index map
(k/v blocks are fetched from head h // group), so KV is never expanded —
each KV block is read once per q-head group member, straight HBM→VMEM.

Causal/window block skipping happens at the `pl.when` level: fully-masked
(q_block, k_block) pairs skip the MXU work entirely.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            bq: int, bk: int, n_kb: int, sq_valid: int, sk_valid: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk

    # static-shape block skip conditions (evaluated on traced grid ids)
    relevant = k_start < sk_valid
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window is not None:
        relevant &= k_start + bk - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # [bq, dh]
        k = k_ref[0, 0].astype(jnp.float32)                   # [bk, dh]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq,bk]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < sk_valid
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                   # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                                # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)                       # [bq, 1]
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_kb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,                 # [B, H, Sq, dh]  (dh padded to 128 upstream)
    k: jax.Array,                 # [B, K, Sk, dh]
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: float,
    sq_valid: int,
    sk_valid: int,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, dh = q.shape
    _, K, Sk, _ = k.shape
    groups = H // K
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    n_kb = Sk // bk

    grid = (B, H, Sq // bq, n_kb)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, n_kb=n_kb, sq_valid=sq_valid, sk_valid=sk_valid)

    return pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, iq, ik: (b, h // groups, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b, h, iq, ik: (b, h // groups, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum-exp l
            pltpu.VMEM((bq, dh), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
