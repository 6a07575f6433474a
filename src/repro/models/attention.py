"""Attention block: projections (+QKV bias), RoPE, flash-attention call.

Weight layout is *head-group-major*: wq [K, D, G·dh], wk/wv [K, D, dh]
(K = kv heads, G = q heads per group), so head order is kv-major
(h = k·G + g), which is exactly the GQA convention the kernels assume
(kv head = h // G).

Exposes split phases (`project_qkv` / `project_out`) so the decode engine
can interpose the paged KV cache between them.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import flash_attention
from repro.models.layers import ParamBuilder, apply_rope, dense, rms_norm


def init_attention(b: ParamBuilder, cfg: ModelConfig, *, cross: bool = False):
    d = cfg.d_model
    K, G, dh = cfg.n_kv_heads, cfg.group_size, cfg.d_head
    b.param("wq_w", (K, d, G * dh), (None, "embed", "heads"))
    b.param("wk_w", (K, d, dh), (None, "embed", "head_dim"))
    b.param("wv_w", (K, d, dh), (None, "embed", "head_dim"))
    if cfg.attn_bias:
        b.param("wq_b", (K, G * dh), (None, "heads"), init="zeros")
        b.param("wk_b", (K, dh), (None, "head_dim"), init="zeros")
        b.param("wv_b", (K, dh), (None, "head_dim"), init="zeros")
    b.param("wo_w", (cfg.q_dim, d), ("heads", "embed"))
    if cfg.qk_norm and not cross:
        b.param("q_norm", (dh,), ("norm",), init="zeros")
        b.param("k_norm", (dh,), ("norm",), init="zeros")
    if cfg.attn_gate and not cross:
        b.param("wgate_w", (K, d, G * dh), (None, "embed", "heads"))


def _proj(p, name: str, x: jax.Array, dequant_fn=None) -> jax.Array:
    """x: [..., D] -> [..., K, f] via head-group-major weight."""
    w = p[f"{name}_w"]
    if type(w).__name__ == "QuantizedWeight":
        from repro.core.quant import dequantize
        w = dequantize(w, x.dtype)
    y = jnp.einsum("...d,kdf->...kf", x, w.astype(x.dtype))
    b = p.get(f"{name}_b")
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def project_qkv(
    params: Dict[str, Any], cfg: ModelConfig, x: jax.Array,
    positions: Optional[jax.Array], *, rope: bool = True,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: [B, S, D] -> q [B, S, H, dh], k/v [B, S, K, dh] (per-head
    RMSNorm on q and k where the layer has it, then RoPE unless `rope` is
    False)."""
    B, S, _ = x.shape
    K, G, dh = cfg.n_kv_heads, cfg.group_size, cfg.d_head
    q = _proj(params, "wq", x).reshape(B, S, K * G, dh)
    k = _proj(params, "wk", x)                                 # [B, S, K, dh]
    v = _proj(params, "wv", x)
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    if rope:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def use_rope(cfg: ModelConfig, is_global) -> bool:
    """Whether a layer ropes q and k: every layer, or only the window
    layers where `cfg.rope_global` is False (global layers are NoPE)."""
    return cfg.rope_global or not is_global


def project_out(params: Dict[str, Any], cfg: ModelConfig,
                attn: jax.Array, x: Optional[jax.Array] = None) -> jax.Array:
    """attn: [B, S, H, dh] -> [B, S, D].  Layers with an output gate
    multiply attn by sigmoid(x Wgate) first (x: the attention's input
    [B, S, D])."""
    B, S = attn.shape[:2]
    attn = attn.reshape(B, S, cfg.q_dim)
    if "wgate_w" in params:
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid(_proj(params, "wgate", x).reshape(
                B, S, cfg.q_dim).astype(jnp.float32))
            attn = (attn.astype(jnp.float32) * gate).astype(attn.dtype)
    return dense(params, "wo", attn)


def attention_train(
    params: Dict[str, Any], cfg: ModelConfig, x: jax.Array, *,
    window: Optional[int] = None, is_global=None, causal: bool = True,
    impl: str = "auto", positions: Optional[jax.Array] = None,
    kv_x: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence attention (train/prefill). kv_x enables cross-attention."""
    if kv_x is None:
        if not cfg.rope_global and is_global is not None:
            # NoPE global layers under a traced flag: RoPE at position 0
            # is the identity
            if positions is None:
                positions = jnp.arange(x.shape[1])[None, :]
            positions = jnp.where(is_global, 0, positions)
        q, k, v = project_qkv(params, cfg, x, positions)
    else:  # cross-attention: queries from x, keys/values from encoder output
        B, S, _ = x.shape
        q = _proj(params, "wq", x).reshape(B, S, cfg.n_heads, cfg.d_head)
        k = _proj(params, "wk", kv_x)
        v = _proj(params, "wv", kv_x)
        causal = False
    out = sharded_flash_attention(q, k, v, causal=causal, window=window,
                                  is_global=is_global, impl=impl)
    return project_out(params, cfg, out, x)


def sharded_flash_attention(q, k, v, *, causal=True, window=None,
                            is_global=None, impl="auto"):
    """Mesh-adaptive attention: ring attention (sequence parallel) when the
    ambient mesh has a model axis > 1, single-device flash otherwise.

    Nesting-aware: inside an outer manual shard_map (the compressed-DP
    train step is manual over pod/data), the inner shard_map must use the
    abstract context mesh and may only map the still-Auto axes.
    """
    from repro.distributed.sharding import get_current_mesh
    mesh = get_current_mesh()
    manual = set() if mesh is None else {
        n for n, t in zip(mesh.axis_names, mesh.axis_types)
        if t == jax.sharding.AxisType.Manual}
    if (mesh is not None and "model" in mesh.shape
            and mesh.shape["model"] > 1 and "model" not in manual
            and q.shape[1] % mesh.shape["model"] == 0
            and q.shape[1] > 1):
        from repro.core.seqpar import ring_attention
        batch_axes, rem = [], q.shape[0]
        for a in ("pod", "data"):
            if a in mesh.shape and a not in manual \
                    and rem % mesh.shape[a] == 0:
                batch_axes.append(a)
                rem //= mesh.shape[a]
        return ring_attention(q, k, v, mesh, causal=causal, window=window,
                              is_global=is_global,
                              batch_axes=tuple(batch_axes),
                              seq_axis="model")
    return flash_attention(q, k, v, causal=causal, window=window,
                           is_global=is_global, impl=impl)
