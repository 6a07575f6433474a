"""Core layers and the ParamBuilder (params + logical-axis specs in one pass).

All parameters are plain pytrees (nested dicts of jnp arrays); a structurally
identical tree of logical-axis tuples is built alongside, which
`distributed.sharding` maps onto any mesh.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# ParamBuilder
# ---------------------------------------------------------------------------


class ParamBuilder:
    """Builds `params` and `specs` trees simultaneously.

    Works under `jax.eval_shape` for allocation-free abstract init (the
    dry-run path): all inits are jax PRNG ops, so tracing records shapes only.
    """

    def __init__(self, rng: jax.Array, dtype=jnp.float32):
        self._rng = rng
        self.dtype = dtype
        self.params: Dict[str, Any] = {}
        self.specs: Dict[str, Any] = {}

    def _next_key(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def param(self, name: str, shape: Sequence[int],
              axes: Sequence[Optional[str]], *, init: str = "normal",
              scale: Optional[float] = None, dtype=None) -> jax.Array:
        assert len(shape) == len(axes), (name, shape, axes)
        dtype = dtype or self.dtype
        if init == "zeros":
            val = jnp.zeros(shape, dtype)
        elif init == "ones":
            val = jnp.ones(shape, dtype)
        else:  # fan-in scaled normal
            if scale is None:
                fan_in = shape[0] if len(shape) == 1 else shape[-2]
                scale = 1.0 / math.sqrt(max(fan_in, 1))
            val = (jax.random.normal(self._next_key(), shape, jnp.float32)
                   * scale).astype(dtype)
        self.params[name] = val
        self.specs[name] = tuple(axes)
        return val

    def scope(self, name: str) -> "ParamBuilder":
        sub = ParamBuilder(self._next_key(), self.dtype)
        self.params[name] = sub.params
        self.specs[name] = sub.specs
        return sub


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_rms_norm(b: ParamBuilder, name: str, dim: int):
    b.param(name, (dim,), ("norm",), init="zeros")


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------

def rope_frequencies(d_head: int, theta: float) -> jax.Array:
    half = d_head // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., seq, heads, d_head]; positions: [..., seq] (int)."""
    d_head = x.shape[-1]
    freqs = rope_frequencies(d_head, theta)                  # [half]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., :, None, :]                   # [..., S, 1, half]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------

def init_dense(b: ParamBuilder, name: str, in_dim: int, out_dim: int,
               axes: Tuple[Optional[str], Optional[str]], bias: bool = False):
    b.param(f"{name}_w", (in_dim, out_dim), axes)
    if bias:
        b.param(f"{name}_b", (out_dim,), (axes[1],), init="zeros")


def dense(params: Dict[str, Any], name: str, x: jax.Array) -> jax.Array:
    w = params[f"{name}_w"]
    if type(w).__name__ == "QuantizedWeight":
        from repro.kernels.quant_gemv import quant_gemv
        y = quant_gemv(x, w)
    else:
        y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    b = params.get(f"{name}_b")
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def init_mlp(b: ParamBuilder, d_model: int, d_ff: int, gated: bool):
    if gated:
        init_dense(b, "gate", d_model, d_ff, ("embed", "mlp"))
        init_dense(b, "up", d_model, d_ff, ("embed", "mlp"))
    else:
        init_dense(b, "up", d_model, d_ff, ("embed", "mlp"))
    init_dense(b, "down", d_ff, d_model, ("mlp", "embed"))


def mlp(params: Dict[str, Any], x: jax.Array, gated: bool) -> jax.Array:
    if gated:
        h = jax.nn.silu(dense(params, "gate", x)) * dense(params, "up", x)
    else:
        h = jax.nn.gelu(dense(params, "up", x))
    return dense(params, "down", h)


def _maybe_dequant(w, dtype):
    if type(w).__name__ == "QuantizedWeight":
        from repro.core.quant import dequantize
        return dequantize(w, dtype)
    return w


# ---------------------------------------------------------------------------
# Mixture of Experts: routing over every expert, experts held here
# ---------------------------------------------------------------------------

# Up to this many rows every held expert runs on every row (weighted by
# its routed weight, zero where not routed): a decode step reads each
# held expert's weights once either way, and at so few rows the expert
# matmuls are bound by those reads.  More rows are sorted into groups.
MOE_DENSE_ROWS = 64


def init_moe(b: ParamBuilder, cfg):
    """Router over all `n_experts`, the `experts_held` experts this chip
    holds, and the shared experts."""
    d, f, E, Eh = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.experts_held
    b.param("router_w", (d, E), ("embed", None))
    if cfg.expert_bias:
        b.param("expert_bias", (E,), (None,), init="zeros")
    b.param("w_gate", (Eh, d, f), ("expert", "embed", "moe_mlp"))
    b.param("w_up", (Eh, d, f), ("expert", "embed", "moe_mlp"))
    b.param("w_down", (Eh, f, d), ("expert", "moe_mlp", "embed"))
    if cfg.n_shared_experts:
        init_mlp(b.scope("shared"), d, cfg.n_shared_experts * f, True)


def moe_route(params: Dict[str, Any], x: jax.Array, cfg):
    """x [N, D] -> (experts [N, top_k] int32, weights [N, top_k] f32).

    Scores are softmax or sigmoid of the router logits, in float32 at
    full precision (selection is a comparison: a rounded logit flips
    near-ties); the top-k is taken on score + `expert_bias`, the weights
    are the chosen scores, divided by their sum and scaled by
    `route_scale`."""
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        params["router_w"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    score = (jax.nn.sigmoid(logits) if cfg.router == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    sel = score
    if "expert_bias" in params:
        sel = score + params["expert_bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(sel, cfg.top_k)
    w = jnp.take_along_axis(score, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * cfg.route_scale


def _expert_weights(params, dtype):
    return [_maybe_dequant(params[k], dtype).astype(dtype)
            for k in ("w_gate", "w_up", "w_down")]


def _experts_dense(params, x, cw):
    """Every held expert on every row of x [N, D], weighted by cw
    [N, E_held] (zero where the expert was not routed)."""
    wg, wu, wd = _expert_weights(params, x.dtype)
    h = (jax.nn.silu(jnp.einsum("nd,edf->nef", x, wg))
         * jnp.einsum("nd,edf->nef", x, wu))
    return jnp.einsum("nef,efd->nd", h * cw[:, :, None].astype(h.dtype), wd)


def _experts_grouped(params, x, local, w, held):
    """Token-expert pairs sorted by held expert and run as ragged groups:
    each pair of x [N, D] routed to held expert `local` [N, k] costs one
    row; pairs on experts held elsewhere sort last, outside every group."""
    N, k = local.shape
    Eh = params["w_gate"].shape[0]
    key = jnp.where(held, local, Eh).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[key].add(1)[:Eh]
    xs = jnp.take(x, order // k, axis=0)
    wg, wu, wd = _expert_weights(params, x.dtype)
    h = (jax.nn.silu(jax.lax.ragged_dot(xs, wg, sizes))
         * jax.lax.ragged_dot(xs, wu, sizes))
    y = jax.lax.ragged_dot(h, wd, sizes)                     # [N*k, D]
    keep = jnp.take(held.reshape(-1), order)
    wt = jnp.take(w.reshape(-1), order).astype(y.dtype)
    y = jnp.where(keep[:, None], y * wt[:, None], 0)
    inv = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    return jnp.take(y, inv, axis=0).reshape(N, k, -1).sum(1)


def _experts_capacity(params, x, local, w, held, capacity_factor):
    """Capacity-based dispatch (training): x [B, T, D], local/w/held
    [B, T, k].  Dispatch is *per batch row* so the dispatched buffer
    [B, E, C, D] shards over both data (B) and model (E) axes — at
    kimi-k2 scale (384 experts, 1M global tokens) a global dispatch
    buffer would not fit.  Position-within-expert uses a sort-based
    ranking (O(T·k) memory) instead of the classic one-hot cumsum
    (O(T·k·E)).  Pairs beyond an expert's capacity, and pairs on experts
    held elsewhere, are dropped (standard in EP training)."""
    B, T, D = x.shape
    top_k = local.shape[-1]
    E = params["w_gate"].shape[0]
    Tk = T * top_k
    C = max(1, math.ceil(capacity_factor * top_k * T / E))

    def route_row(xt, loc, hd):
        fe = jnp.where(hd, loc, E).reshape(-1)                     # [Tk]
        order = jnp.argsort(fe, stable=True)
        counts = jnp.zeros((E + 1,), jnp.int32).at[fe].add(1)
        starts = jnp.cumsum(counts) - counts                       # [E+1]
        pos_sorted = jnp.arange(Tk, dtype=jnp.int32) - starts[fe[order]]
        pos = jnp.zeros((Tk,), jnp.int32).at[order].set(pos_sorted)
        keep = (pos < C) & (fe < E)
        tok_ids = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
        slot = jnp.where(keep, fe * C + pos, E * C)                # drop -> OOB
        dispatched = jnp.zeros((E * C + 1, D), xt.dtype).at[slot].set(
            xt[tok_ids])[:-1].reshape(E, C, D)
        return dispatched, slot, keep, tok_ids

    dispatched, slot, keep, tok_ids = jax.vmap(route_row)(x, local, held)

    # expert computation (grouped einsum; expert axis sharded -> EP)
    wg, wu, wd = _expert_weights(params, x.dtype)
    h = (jax.nn.silu(jnp.einsum("becd,edf->becf", dispatched, wg))
         * jnp.einsum("becd,edf->becf", dispatched, wu))
    out = jnp.einsum("becf,efd->becd", h, wd)                      # [B, E, C, D]

    def combine_row(out_row, slot_row, keep_row, tok_row, vals):
        out_flat = out_row.reshape(E * C, D)
        safe = jnp.where(slot_row < E * C, slot_row, 0)
        gathered = jnp.where(keep_row[:, None], out_flat[safe], 0.0)
        weighted = gathered * vals.reshape(-1)[:, None].astype(out_flat.dtype)
        return jnp.zeros((T, D), out_flat.dtype).at[tok_row].add(weighted)

    return jax.vmap(combine_row)(out, slot, keep, tok_ids, w)


def moe(params: Dict[str, Any], x: jax.Array, cfg, *,
        capacity_factor: Optional[float] = None,
        rows: Optional[jax.Array] = None):
    """MoE FFN of x [..., D] over the experts this chip holds.

    Routing covers all `cfg.n_experts`; only the held experts
    [`expert_offset`, + `experts_held`) are computed, weighted by their
    routed weights, and the shared experts are added.  Dropless unless
    `capacity_factor` is given (the training dispatch; x must then be
    [B, T, D]): a row's output depends on that row alone.

    Returns (y [..., D], held pairs): the count of token-expert pairs
    routed to held experts, over the rows where `rows` [N] is True (all
    rows when None)."""
    shape = x.shape
    xf = x.reshape(-1, shape[-1])
    Eh = params["w_gate"].shape[0]
    with jax.named_scope("moe_route"):
        idx, w = moe_route(params, xf, cfg)
        local = idx - cfg.expert_offset
        held = (local >= 0) & (local < Eh)
        counted = held if rows is None else held & rows[:, None]
        n_held = jnp.sum(counted, dtype=jnp.int32)
    with jax.named_scope("moe_experts"):
        if capacity_factor is not None:
            k = cfg.top_k
            y = _experts_capacity(
                params, x, local.reshape(shape[:-1] + (k,)),
                w.reshape(shape[:-1] + (k,)),
                held.reshape(shape[:-1] + (k,)), capacity_factor)
            y = y.reshape(xf.shape)
        elif xf.shape[0] <= MOE_DENSE_ROWS:
            cw = jnp.sum(jnp.where(
                held[..., None],
                w[..., None] * jax.nn.one_hot(local, Eh, dtype=w.dtype),
                0), axis=1)                                    # [N, Eh]
            y = _experts_dense(params, xf, cw)
        else:
            y = _experts_grouped(params, xf, local, w, held)
    if "shared" in params:
        with jax.named_scope("moe_shared"):
            y = y + mlp(params["shared"], xf, True)
    return y.reshape(shape), n_held


def moe_aux_loss(params: Dict[str, Any], x: jax.Array, top_k: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss (fraction·prob product)."""
    E = params["router_w"].shape[-1]
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router_w"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)
    _, top_idx = jax.lax.top_k(gates, top_k)
    frac = jnp.mean(jax.nn.one_hot(top_idx, E, dtype=jnp.float32), axis=(0, 1, 2))
    prob = jnp.mean(gates, axis=(0, 1))
    return E * jnp.sum(frac * prob)


# ---------------------------------------------------------------------------
# Embedding / loss
# ---------------------------------------------------------------------------

def init_embedding(b: ParamBuilder, vocab: int, d_model: int,
                   name: str = "embedding"):
    # 1/sqrt(d) keeps tied-lm-head logits O(1) at init
    b.param(name, (vocab, d_model), ("vocab", "embed"),
            scale=d_model ** -0.5)


def embed_lookup(table: jax.Array, ids: jax.Array, dtype) -> jax.Array:
    return jnp.take(table, ids, axis=0).astype(dtype)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       true_vocab: int) -> jax.Array:
    """Mean CE over labels >= 0, masking padded vocab entries."""
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if true_vocab < V:
        neg = jnp.full((V - true_vocab,), -1e9, logits.dtype)
        logits = logits.at[..., true_vocab:].add(neg)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
