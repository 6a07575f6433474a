"""jit'd public wrapper for flash attention with impl dispatch.

impl:
  auto      -> pallas on TPU backends, ref elsewhere (CPU dry-run / tests)
  pallas    -> force the TPU kernel
  interpret -> Pallas interpret mode (kernel body on CPU; used by tests)
  ref       -> pure-jnp blocked oracle
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "impl", "block_q", "block_k"))
def flash_attention(
    q: jax.Array,                  # [B, Sq, H, dh]
    k: jax.Array,                  # [B, Sk, K, dh]
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset=0,
    is_global=None,
    impl: str = "auto",
    block_q: int = 512,
    block_k: int = 512,
) -> jax.Array:
    dynamic = is_global is not None or not isinstance(q_offset, int)
    if impl == "auto":
        # dynamic window toggles / traced offsets have no kernel
        impl = "ref" if dynamic else default_impl()
    elif dynamic and impl != "ref":
        raise ValueError(
            f"impl={impl!r}: the flash kernel takes a static window and "
            "offset; dynamic flags (is_global) or a traced q_offset run "
            "only with impl='ref' or 'auto'")
    if impl == "ref":
        return flash_attention_ref(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset, is_global=is_global,
                                   chunk_k=block_k)

    B, Sq, H, dh = q.shape
    _, Sk, K, _ = k.shape
    scale = dh ** -0.5

    # [B, S, H, dh] -> [B, H, S, dh]; pad dh to the 128-lane MXU width and
    # sequence dims to block multiples (zero keys are masked via sk_valid).
    qt = _pad_to(q.transpose(0, 2, 1, 3), 3, 128)
    kt = _pad_to(k.transpose(0, 2, 1, 3), 3, 128)
    vt = _pad_to(v.transpose(0, 2, 1, 3), 3, 128)
    bq = min(block_q, max(Sq, 16))
    bk = min(block_k, max(Sk, 16))
    qt = _pad_to(qt, 2, bq)
    kt = _pad_to(kt, 2, bk)
    vt = _pad_to(vt, 2, bk)

    out = flash_attention_pallas(
        qt, kt, vt, causal=causal, window=window, scale=scale,
        sq_valid=Sq, sk_valid=Sk, block_q=bq, block_k=bk,
        interpret=(impl == "interpret"))
    return out[:, :, :Sq, :dh].transpose(0, 2, 1, 3)
