"""The work the algorithm needs, counted from shapes and the client's
token records (never from what a kernel happened to walk), so that a
later change that walks less raises the shares legitimately.

Per decoded token at context length c (keys it attends over), in every
layer: c * (K + V bytes per token) of KV reads and 4 * n_heads * d_head
* c FLOPs (q k^T and p v).  Model FLOPs per token: 2 per matmul
parameter of every layer (`dm.layer_matmul_params`, from the
configuration's reference module), 2 * vocab * d_model for the head
where logits are needed, plus the attention FLOPs above.
"""
from __future__ import annotations

from typing import Tuple

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def kv_bytes_per_token(dm, kv_bytes: int) -> int:
    """K and V of one token in one layer."""
    return 2 * dm.K * dm.dh * kv_bytes


def attn_flops(dm, c: int) -> int:
    """Attention FLOPs of one query over c keys, all layers."""
    return 4 * dm.H * dm.dh * c * dm.L


def decode_token(dm, c: int, kv_bytes: int) -> Tuple[int, int, int]:
    """(attention FLOPs, KV bytes, model FLOPs) of one decoded token
    attending over c keys."""
    af = attn_flops(dm, c)
    kb = c * kv_bytes_per_token(dm, kv_bytes) * dm.L
    mf = 2 * dm.L * dm.layer_matmul_params + 2 * dm.V * dm.d + af
    return af, kb, mf


def prefill_flops(dm, n: int) -> int:
    """Model FLOPs of prefilling an n-token prompt (causal attention,
    logits at its last position only)."""
    return (2 * dm.L * dm.layer_matmul_params * n + 2 * dm.V * dm.d
            + attn_flops(dm, 1) * n * (n + 1) // 2)


def window_work(records, dm, kv_bytes: int, t0: float, t1: float):
    """Work of the tokens that reached clients inside [t0, t1): returns
    (decode attention FLOPs, decode KV bytes, model FLOPs, decoded
    tokens).  Served token j >= 1 of a request with an n-token prompt
    came from a decode step attending over n + j keys; token 0 ends the
    prompt's prefill, whose FLOPs are counted when it arrives."""
    af = kb = mf = nd = 0
    for r in records:
        for j, t in enumerate(r.times):
            if not t0 <= t < t1:
                continue
            if j == 0:
                mf += prefill_flops(dm, r.n_prompt)
                continue
            a, b, m = decode_token(dm, r.n_prompt + j, kv_bytes)
            af, kb, mf, nd = af + a, kb + b, mf + m, nd + 1
    return af, kb, mf, nd
