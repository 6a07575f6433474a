"""Pallas TPU quantized-GEMV kernel (the IFC weight-GEMV analogue).

The decode-phase GEMV is pure weight streaming: arithmetic intensity ≈ 1
op/byte at bf16, ≈ 4 ops/byte at int4.  The kernel tiles the weight matrix
[D, F] into (bd × bf) VMEM blocks, dequantizes in-register (nibble unpack +
per-channel scale), and accumulates x·W in an f32 VMEM scratch across the
sequential D dimension — weights are read exactly once, the activation
block is tiny, so HBM traffic ≈ quantized weight bytes (the paper's W4A16
bandwidth win, §V Takeaway 2).

Grid: (F_tiles, D_tiles), D innermost/sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel_w4(x_ref, q_ref, s_ref, o_ref, acc_scr, *, n_d: int):
    idx = pl.program_id(1)

    @pl.when(idx == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(jnp.bfloat16)                      # [M, bd]
    qp = q_ref[...]                                          # [bd/2, bf] uint8
    hi = ((qp >> 4) & 0xF).astype(jnp.int8) - 8
    lo = (qp & 0xF).astype(jnp.int8) - 8
    bd2, bf = qp.shape
    w = jnp.stack([hi, lo], axis=1).reshape(2 * bd2, bf)     # [bd, bf]
    acc_scr[...] += jax.lax.dot_general(
        x, w.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(idx == n_d - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def _kernel_w8(x_ref, q_ref, s_ref, o_ref, acc_scr, *, n_d: int):
    idx = pl.program_id(1)

    @pl.when(idx == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # int8 × int8 → int32 accumulate (MXU int path); x pre-quantized upstream
    x = x_ref[...].astype(jnp.int8)
    w = q_ref[...].astype(jnp.int8)
    acc_scr[...] += jax.lax.dot_general(
        x.astype(jnp.int32), w.astype(jnp.int32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32).astype(jnp.float32)

    @pl.when(idx == n_d - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def quant_gemv_pallas(x, q, scale, scheme: str, *, block_d: int = 512,
                      block_f: int = 512, interpret: bool = False,
                      out_dtype=jnp.float32):
    """x: [M, D] (bf16 for w4, int8 for w8); q: packed weights; scale: [F]."""
    M, D = x.shape
    F = q.shape[-1]
    bd = min(block_d, D)
    bf = min(block_f, F)
    assert D % bd == 0 and F % bf == 0, (D, bd, F, bf)
    n_d = D // bd

    if scheme == "w4a16":
        kernel = functools.partial(_kernel_w4, n_d=n_d)
        q_spec = pl.BlockSpec((bd // 2, bf), lambda f, d: (d, f))
    else:
        kernel = functools.partial(_kernel_w8, n_d=n_d)
        q_spec = pl.BlockSpec((bd, bf), lambda f, d: (d, f))

    return pl.pallas_call(
        kernel,
        name="quant_gemv",
        grid=(F // bf, n_d),
        in_specs=[
            pl.BlockSpec((M, bd), lambda f, d: (0, d)),
            q_spec,
            pl.BlockSpec((bf,), lambda f, d: (f,)),
        ],
        out_specs=pl.BlockSpec((M, bf), lambda f, d: (0, f)),
        out_shape=jax.ShapeDtypeStruct((M, F), out_dtype),
        scratch_shapes=[pltpu.VMEM((M, bf), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x, q, scale)
