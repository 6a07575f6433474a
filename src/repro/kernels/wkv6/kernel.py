"""Pallas TPU kernel for the RWKV6 wkv recurrence (chunked).

Grid: (B, H, n_chunks) with chunks innermost/sequential — the [dh, dh]
state matrix lives in VMEM scratch across chunks (never touching HBM
between chunks, unlike the jnp chunked form whose carried state and
per-chunk cumulative-decay tensors round-trip).  Within a chunk the
cumprod factorization of models/rwkv6.py runs on MXU dots:

    out = (A ⊙ tril) v  +  diag-bonus  +  (r·a_t) S_chunk_start
    S'  = e^{total} S + (k e^{total-cum})ᵀ v

Inputs arrive pre-transposed [B, H, S, dh] (ops.py), decay as log values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sT_ref,
            state_scr, *, chunk: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)          # [T, dh]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)        # log-decay per k-channel
    u = u_ref[0].astype(jnp.float32)             # [1, dh] bonus

    cum = jnp.cumsum(lw, axis=0)                 # inclusive
    cum_excl = cum - lw
    total = cum[-1:, :]                          # [1, dh]

    r_a = r * jnp.exp(cum_excl)                  # r_t · a_t
    k_b = k * jnp.exp(-cum)                      # k_i / (a_i w_i)
    k_last = k * jnp.exp(total - cum)

    # intra-chunk: A[t, i] = (r_t a_t)·(k_i e^{-cum_i}) for i < t
    A = jax.lax.dot_general(r_a, k_b, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [T, T]
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    A = jnp.where(tj < ti, A, 0.0)
    intra = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)        # [T, 1]
    intra = intra + diag * v

    # inter-chunk: (r_t a_t) · S_chunk_start
    S = state_scr[...]                                        # [dh, dh]
    inter = jax.lax.dot_general(r_a, S, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0, 0] = (intra + inter).astype(o_ref.dtype)

    kv = jax.lax.dot_general(k_last, v, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    state_scr[...] = jnp.exp(total).T * S + kv

    @pl.when(c == n_chunks - 1)
    def _final():
        sT_ref[0, 0] = state_scr[...]


def wkv6_pallas(r, k, v, logw, u, s0, *, chunk: int = 32,
                interpret: bool = False):
    """r/k/v/logw: [B, H, S, dh]; u: [H, dh]; s0: [B, H, dh, dh].

    Returns (out [B, H, S, dh] f32, sT [B, H, dh, dh] f32).
    """
    B, H, S, dh = r.shape
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    grid = (B, H, n_chunks)
    seq_spec = pl.BlockSpec((1, 1, chunk, dh),
                            lambda b, h, c: (b, h, c, 0))
    kernel = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, dh), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, dh, dh), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, dh), jnp.float32),
            jax.ShapeDtypeStruct((B, H, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(r, k, v, logw, u, s0)
