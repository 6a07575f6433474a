"""Unified model zoo: one stacked-layer decoder covering all six families.

Layers are *stacked* (leading `layer` axis on every per-layer param) and
executed with `lax.scan`, which keeps compile time flat in depth (61–80-layer
configs) — essential for the 40-cell dry-run.  Per-layer heterogeneity
(gemma3 local:global, hymba sparse-global) rides along as scanned boolean
flag arrays, not unrolled python branching.

Families:
  dense / moe / vlm : pre-norm attention + (SwiGLU | MoE) FFN
  ssm (rwkv6)       : time-mix (wkv) + channel-mix
  hybrid (hymba)    : parallel attention + mamba heads, averaged
  audio (whisper)   : bidirectional encoder + causal decoder w/ cross-attn
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import rwkv6, ssm
from repro.models.layers import (
    ParamBuilder, cross_entropy_loss, dense, embed_lookup, init_dense,
    init_embedding, init_mlp, init_moe, init_rms_norm, mlp, moe, moe_aux_loss,
    rms_norm,
)

# ---------------------------------------------------------------------------
# Runtime (static) knobs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Runtime:
    activ_dtype: Any = jnp.float32
    attn_impl: str = "auto"          # flash attention dispatch
    moe_capacity: Optional[float] = 1.25   # None: dropless MoE
    vlm_patches: int = 256           # stub patch-prefix length (pixtral)
    enc_frames_ratio: int = 4        # whisper: frames = seq_len // ratio
    loss_chunk: int = 0              # >0: sequence-chunked CE (remat'd per
    #                                  chunk — one chunk of logits live at
    #                                  a time instead of [B, S, V])


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def _init_block(b: ParamBuilder, cfg: ModelConfig, dense: bool = False):
    """One decoder block (params WITHOUT the layer axis; stacked by caller);
    `dense`: one of the leading dense-FFN layers of an MoE model."""
    init_rms_norm(b, "ln1", cfg.d_model)
    if cfg.family == "ssm":
        rwkv6.init_rwkv_timemix(b.scope("tmix"), cfg)
        init_rms_norm(b, "ln2", cfg.d_model)
        cm = b.scope("cmix")
        cm.param("mu_k", (cfg.d_model,), ("ssm",), init="zeros")
        cm.param("mu_r", (cfg.d_model,), ("ssm",), init="zeros")
        init_dense(cm, "ck", cfg.d_model, cfg.d_ff, ("embed", "mlp"))
        init_dense(cm, "cv", cfg.d_ff, cfg.d_model, ("mlp", "embed"))
        init_dense(cm, "cr", cfg.d_model, cfg.d_model, ("embed", "heads"))
        return
    attn_mod.init_attention(b.scope("attn"), cfg)
    if cfg.family == "hybrid":
        ssm.init_ssm(b.scope("ssm"), cfg)
    if cfg.is_encoder_decoder:
        init_rms_norm(b, "ln_cross", cfg.d_model)
        attn_mod.init_attention(b.scope("cross"), cfg, cross=True)
    init_rms_norm(b, "ln2", cfg.d_model)
    if cfg.sandwich_norm:
        init_rms_norm(b, "ln1_post", cfg.d_model)
        init_rms_norm(b, "ln2_post", cfg.d_model)
    if dense:
        init_mlp(b.scope("mlp"), cfg.d_model, cfg.dense_d_ff or cfg.d_ff,
                 cfg.gated_mlp)
    elif cfg.is_moe:
        init_moe(b.scope("moe"), cfg)
    else:
        init_mlp(b.scope("mlp"), cfg.d_model, cfg.d_ff, cfg.gated_mlp)


def _init_stacked_layers(b: ParamBuilder, cfg: ModelConfig, n_layers: int,
                         name: str, encoder: bool = False,
                         dense: bool = False):
    """Init `n_layers` blocks with a leading `layer` axis on every leaf.

    vmap over per-layer PRNG keys stacks every leaf while preserving each
    parameter's proper initializer (zeros/ones/fan-in normal).
    """
    cfg_blk = cfg if not encoder else dataclasses.replace(
        cfg, family="dense", is_encoder_decoder=False, n_kv_heads=cfg.n_heads)

    def one(key):
        pb = ParamBuilder(key, b.dtype)
        _init_block(pb, cfg_blk, dense)
        return pb.params

    keys = jax.random.split(b._next_key(), n_layers)
    b.params[name] = jax.vmap(one)(keys)

    proto = ParamBuilder(jax.random.PRNGKey(0), b.dtype)
    _init_block(proto, cfg_blk, dense)
    b.specs[name] = jax.tree.map(
        lambda sp: ("layer",) + tuple(sp), proto.specs,
        is_leaf=lambda x: isinstance(x, tuple))


def init_model(cfg: ModelConfig, rng: jax.Array, dtype=jnp.float32):
    """Returns (params, specs) — structurally identical trees."""
    b = ParamBuilder(rng, dtype)
    init_embedding(b, cfg.padded_vocab, cfg.d_model)
    if cfg.n_dense_layers:
        _init_stacked_layers(b, cfg, cfg.n_dense_layers, "dense_layers",
                             dense=True)
    _init_stacked_layers(b, cfg, cfg.n_layers - cfg.n_dense_layers, "layers")
    init_rms_norm(b, "final_norm", cfg.d_model)
    if not cfg.tie_embeddings:
        b.param("lm_head", (cfg.padded_vocab, cfg.d_model), ("vocab", "embed"))
    if cfg.is_encoder_decoder:
        _init_stacked_layers(b, cfg, cfg.encoder_layers, "encoder",
                             encoder=True)
        init_rms_norm(b, "encoder_norm", cfg.d_model)
    if cfg.n_meta_tokens:
        b.param("meta_tokens", (cfg.n_meta_tokens, cfg.d_model),
                (None, "embed"), scale=0.02)
    return b.params, b.specs


def abstract_params(cfg: ModelConfig, dtype=jnp.float32):
    """Allocation-free (ShapeDtypeStruct) params + specs, for the dry-run.

    The logical-axis spec tree is built by python side effects during the
    eval_shape trace, so no parameter memory is ever allocated.
    """
    holder = {}

    def f(k):
        params, specs = init_model(cfg, k, dtype)
        holder["specs"] = specs
        return params

    aparams = jax.eval_shape(f, jax.random.PRNGKey(0))
    return aparams, holder["specs"]


def layer_stacks(cfg: ModelConfig) -> Tuple[Tuple[str, int, int], ...]:
    """(param stack, first layer, layers) of each stack in run order: the
    leading dense-FFN layers, then the rest."""
    nd = cfg.n_dense_layers
    lead = (("dense_layers", 0, nd),) if nd else ()
    return lead + (("layers", nd, cfg.n_layers - nd),)


# layer-flag arrays (scanned along the layer axis)
def layer_flags(cfg: ModelConfig, first: int = 0,
                n: Optional[int] = None) -> Dict[str, jax.Array]:
    n = cfg.n_layers - first if n is None else n
    is_global = np.array([cfg.is_global_layer(i)
                          for i in range(first, first + n)])
    return {"is_global": jnp.asarray(is_global)}


def ffn(pl_, cfg: ModelConfig, h, *, capacity_factor=None, rows=None):
    """The layer's FFN: its dense MLP, or the MoE (dropless unless a
    capacity is given).  Returns (y, held pairs or None)."""
    if "moe" in pl_:
        return moe(pl_["moe"], h, cfg, capacity_factor=capacity_factor,
                   rows=rows)
    return mlp(pl_["mlp"], h, cfg.gated_mlp), None


def post_norm(pl_, name: str, cfg: ModelConfig, y):
    """Sandwich norm of a sub-block's output, where the layer has one."""
    return rms_norm(y, pl_[name], cfg.norm_eps) if name in pl_ else y


# ---------------------------------------------------------------------------
# Blocks (train / prefill — full sequence)
# ---------------------------------------------------------------------------

def _attn_ffn_block(pl_, cfg: ModelConfig, x, flags, rt: Runtime,
                    positions, enc_out=None):
    """Standard block; handles dense/moe/vlm/audio-decoder/hybrid."""
    window = None if cfg.window is None else cfg.window
    is_global = flags["is_global"] if cfg.window is not None else None
    h = rms_norm(x, pl_["ln1"], cfg.norm_eps)
    aout = attn_mod.attention_train(
        pl_["attn"], cfg, h, window=window, is_global=is_global,
        impl=rt.attn_impl, positions=positions)
    if cfg.family == "hybrid":
        B = x.shape[0]
        state0 = jnp.zeros(ssm.ssm_state_shape(cfg, B), jnp.float32)
        tail0 = jnp.zeros((B, ssm.CONV_K - 1, cfg.d_model), x.dtype)
        sout, _, _ = ssm.ssm_mixer(pl_["ssm"], cfg, h, state0, tail0)
        aout = (aout + sout) * 0.5
    x = x + post_norm(pl_, "ln1_post", cfg, aout)
    if enc_out is not None:
        h = rms_norm(x, pl_["ln_cross"], cfg.norm_eps)
        x = x + attn_mod.attention_train(pl_["cross"], cfg, h, kv_x=enc_out,
                                         impl=rt.attn_impl)
    h = rms_norm(x, pl_["ln2"], cfg.norm_eps)
    ff, _ = ffn(pl_, cfg, h, capacity_factor=rt.moe_capacity)
    aux = (moe_aux_loss(pl_["moe"], h, cfg.top_k) if "moe" in pl_
           else jnp.zeros((), jnp.float32))
    return x + post_norm(pl_, "ln2_post", cfg, ff), aux


def _rwkv_block(pl_, cfg: ModelConfig, x, rt: Runtime):
    B = x.shape[0]
    h = rms_norm(x, pl_["ln1"], cfg.norm_eps)
    state0 = jnp.zeros(rwkv6.rwkv_state_shape(cfg, B), jnp.float32)
    shift0 = jnp.zeros((B, cfg.d_model), x.dtype)
    tout, _, _ = rwkv6.rwkv_timemix(pl_["tmix"], cfg, h, state0, shift0)
    x = x + tout
    h = rms_norm(x, pl_["ln2"], cfg.norm_eps)
    cm = pl_["cmix"]
    h_prev = jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]], axis=1)
    xk = h + (h_prev - h) * cm["mu_k"].astype(h.dtype)
    xr = h + (h_prev - h) * cm["mu_r"].astype(h.dtype)
    k = jnp.square(jax.nn.relu(dense(cm, "ck", xk)))
    v = dense(cm, "cv", k)
    r = jax.nn.sigmoid(dense(cm, "cr", xr))
    return x + r * v, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Full forward (train)
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tok, rt: Runtime):
    """Token embeddings [..., D], times `cfg.embed_scale` where set."""
    x = embed_lookup(params["embedding"], tok, rt.activ_dtype)
    return x * cfg.embed_scale if cfg.embed_scale != 1.0 else x


def embed_inputs(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                 rt: Runtime):
    """Builds the input activation sequence [B, S, D] + positions [B, S].

    vlm: [patch embeddings | token embeddings]; audio: decoder tokens only
    (encoder frames handled separately); hybrid: meta tokens prepended.
    """
    tok = batch["tokens"]
    x = embed_tokens(params, cfg, tok, rt)
    parts = [x]
    if cfg.family == "vlm" and "patches" in batch:
        parts.insert(0, batch["patches"].astype(rt.activ_dtype))
    if cfg.n_meta_tokens:
        B = tok.shape[0]
        meta = jnp.broadcast_to(
            params["meta_tokens"].astype(rt.activ_dtype)[None],
            (B, cfg.n_meta_tokens, cfg.d_model))
        parts.insert(0, meta)
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else x
    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                 (x.shape[0], x.shape[1]))
    return x, positions


def run_layers(params, cfg: ModelConfig, x, rt: Runtime, positions,
               enc_out=None, remat: str = "none", stack: str = "layers",
               layer_constrain=None):
    """lax.scan over stacked layers; returns (x, aux_loss_sum).

    layer_constrain: optional fn applied to the sliced per-layer params
    INSIDE the (remat'd) body — used to gather ZeRO-3/fsdp shards one layer
    at a time.  Without it XLA hoists the all-gather of the ENTIRE stacked
    parameter array into the loop (measured 5.4 TB/device/step at kimi-k2
    scale) and all-reduces full-stack gradients per iteration.
    """
    if stack == "encoder":
        stacks = [("encoder", {"is_global": jnp.ones((cfg.encoder_layers,),
                                                      bool)})]
    else:   # the decoder: leading dense-FFN layers first, then the rest
        stacks = [(name, layer_flags(cfg, first, n))
                  for name, first, n in layer_stacks(cfg)]

    def body(carry, layer_in):
        xc, aux = carry
        pl_, fl = layer_in
        if layer_constrain is not None:
            pl_, xc = layer_constrain(pl_, xc)
        if cfg.family == "ssm":
            xn, a = _rwkv_block(pl_, cfg, xc, rt)
        elif stack == "encoder":
            cfg_enc = dataclasses.replace(
                cfg, family="dense", is_encoder_decoder=False,
                n_kv_heads=cfg.n_heads)
            h = rms_norm(xc, pl_["ln1"], cfg.norm_eps)
            aout = attn_mod.attention_train(pl_["attn"], cfg_enc, h,
                                            causal=False, impl=rt.attn_impl,
                                            positions=positions)
            xc2 = xc + aout
            h = rms_norm(xc2, pl_["ln2"], cfg.norm_eps)
            xn = xc2 + mlp(pl_["mlp"], h, cfg.gated_mlp)
            a = jnp.zeros((), jnp.float32)
        else:
            xn, a = _attn_ffn_block(pl_, cfg, xc, fl, rt, positions, enc_out)
        return (xn, aux + a), None

    if remat in ("block", "full"):
        policy = None if remat == "full" else \
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    aux = jnp.zeros((), jnp.float32)
    for name, flags in stacks:
        (x, aux), _ = jax.lax.scan(body, (x, aux), (params[name], flags))
    return x, aux


def lm_head_logits(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params.get("lm_head", params["embedding"])
    return jnp.einsum("bsd,vd->bsv", x, table.astype(x.dtype))


def forward_train(params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                  rt: Runtime, remat: str = "none",
                  layer_constrain=None) -> Tuple[jax.Array, jax.Array]:
    """Full forward; returns (logits over the token positions, aux_loss)."""
    x, positions = embed_inputs(params, cfg, batch, rt)
    enc_out = None
    if cfg.is_encoder_decoder:
        enc = batch["frames"].astype(rt.activ_dtype)
        enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None],
                                   enc.shape[:2])
        enc_out, _ = run_layers(params, cfg, enc, rt, enc_pos,
                                remat=remat, stack="encoder")
        enc_out = rms_norm(enc_out, params["encoder_norm"], cfg.norm_eps)
    x, aux = run_layers(params, cfg, x, rt, positions, enc_out, remat=remat,
                        layer_constrain=layer_constrain)
    # strip non-token prefixes (meta tokens / patches) before the LM head
    prefix = x.shape[1] - batch["tokens"].shape[1]
    if prefix:
        x = x[:, prefix:]
    return lm_head_logits(params, cfg, x), aux


def chunked_ce(params, cfg: ModelConfig, x: jax.Array, labels: jax.Array,
               chunk: int) -> jax.Array:
    """Sequence-chunked cross entropy: the LM head + softmax run one
    [B, chunk, V] block at a time under jax.checkpoint, so only a single
    chunk of logits is ever live (full [B, S, V] logits are the dominant
    train-step temp allocation at 150K–260K vocabs)."""
    B, S, D = x.shape
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = x.shape[1] // chunk
    xc = x.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, chunk).transpose(1, 0, 2)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one(args):
        xch, lch = args
        logits = lm_head_logits(params, cfg, xch)
        V = logits.shape[-1]
        lg = logits.astype(jnp.float32)
        if cfg.vocab_size < V:
            neg = jnp.full((V - cfg.vocab_size,), -1e9, lg.dtype)
            lg = lg.at[..., cfg.vocab_size:].add(neg)
        logz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(
            lg, jnp.maximum(lch, 0)[..., None], axis=-1)[..., 0]
        mask = (lch >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * mask), jnp.sum(mask)

    def body(carry, args):
        nll, cnt = one(args)
        return (carry[0] + nll, carry[1] + cnt), None

    (nll, cnt), _ = jax.lax.scan(body, (0.0, 0.0), (xc, lc))
    return nll / jnp.maximum(cnt, 1.0)


def loss_fn(params, cfg: ModelConfig, batch, rt: Runtime,
            remat: str = "none",
            layer_constrain=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """batch: tokens [B, S] (inputs) and labels [B, S] (pre-shifted)."""
    if rt.loss_chunk:
        x, positions = embed_inputs(params, cfg, batch, rt)
        enc_out = None
        if cfg.is_encoder_decoder:
            enc = batch["frames"].astype(rt.activ_dtype)
            enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None],
                                       enc.shape[:2])
            enc_out, _ = run_layers(params, cfg, enc, rt, enc_pos,
                                    remat=remat, stack="encoder")
            enc_out = rms_norm(enc_out, params["encoder_norm"],
                               cfg.norm_eps)
        x, aux = run_layers(params, cfg, x, rt, positions, enc_out,
                            remat=remat, layer_constrain=layer_constrain)
        prefix = x.shape[1] - batch["tokens"].shape[1]
        if prefix:
            x = x[:, prefix:]
        ce = chunked_ce(params, cfg, x, batch["labels"], rt.loss_chunk)
        loss = ce + 0.01 * aux
        return loss, {"ce": ce, "aux": aux}
    logits, aux = forward_train(params, cfg, batch, rt, remat=remat,
                                layer_constrain=layer_constrain)
    ce = cross_entropy_loss(logits, batch["labels"], cfg.vocab_size)
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}
