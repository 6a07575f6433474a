"""Per-cell step builders: (arch × shape × mesh) → jit-able fn + abstract
inputs + sharding trees.  Used by the dry-run, the drivers, and benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (
    SHAPES, EngineConfig, ModelConfig, ShapeConfig, get_config,
    shape_applicable,
)
from repro.core.engine import KVNANDEngine, ShardPlan, plan_sharding
from repro.core.quant import quantize_params_and_specs
from repro.distributed import sharding as shd
from repro.models import transformer
from repro.models.registry import batch_sharding_axes, input_specs
from repro.models.transformer import Runtime
from repro.training import optimizer as opt_mod
from repro.training.train_step import TrainState, make_train_step


FSDP_THRESHOLD = 8e9          # params above this shard over `data` too
BF16_MOMENTS_THRESHOLD = 3e11  # kimi-scale: bf16 AdamW moments


def runtime_for(cfg: ModelConfig, kind: str = "serve") -> Runtime:
    # train: sequence-chunked CE — full [B, S, V] logits are the dominant
    # temp allocation at 150K–260K vocabs (§Perf iteration T1)
    return Runtime(activ_dtype=jnp.bfloat16, attn_impl="ref",
                   loss_chunk=1024 if kind == "train" else 0)


def engine_config_for(cfg: ModelConfig, shape: ShapeConfig,
                      overrides: Optional[Dict[str, Any]] = None
                      ) -> EngineConfig:
    kw: Dict[str, Any] = dict(remat="block")
    if shape.kind == "decode":
        # The compact plan computes page validity/masks once instead of
        # per head-group, and the paper's HG overlap is a property of
        # fixed-function flash dies that a TPU's fungible MXUs do not
        # share — so compact is the decode default.  (Not measured on a
        # chip.)  The paper-faithful discrete plan remains selectable
        # (--variant discrete).
        kw.update(variant="compact")
    if overrides:
        kw.update(overrides)
    return EngineConfig(**kw)


def _axes_or_none(axes: Tuple[str, ...]):
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def _sh(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


# ---------------------------------------------------------------------------
# sharding trees
# ---------------------------------------------------------------------------

def batch_shardings(cfg, shape, mesh, rules):
    specs = input_specs(cfg, shape, runtime_for(cfg))
    axes = batch_sharding_axes(cfg, shape)
    out = {}
    for name, sds in specs.items():
        out[name] = NamedSharding(
            mesh, shd.spec_for_shape(sds.shape, axes[name], rules, mesh))
    return out


def cache_shardings(cache, mesh: Mesh, plan: ShardPlan):
    b = _axes_or_none(plan.batch_axes)
    pg = _axes_or_none(plan.page_axes_g)
    pw = _axes_or_none(plan.page_axes_w)
    field_specs = {
        "k_pages_g": P(None, b, None, pg, None, None),
        "v_pages_g": P(None, b, None, pg, None, None),
        "page_table_g": P(b, None),
        "k_pages_w": P(None, b, None, pw, None, None),
        "v_pages_w": P(None, b, None, pw, None, None),
        "page_table_w": P(b, None),
        "page_pos_w": P(b, None),
        "k_scale_g": P(None, b, None, pg),
        "v_scale_g": P(None, b, None, pg),
        "k_scale_w": P(None, b, None, pw),
        "v_scale_w": P(None, b, None, pw),
        "rwkv_state": P(None, b, None, None, None),
        "rwkv_shift": P(None, b, None),
        "rwkv_shift2": P(None, b, None),
        "ssm_state": P(None, b, None, None),
        "conv_tail": P(None, b, None, None),
        "cross_k": P(None, b, "model", None, None),
        "cross_v": P(None, b, "model", None, None),
        "lengths": P(b),
    }
    # shared-pool leaves drop the batch dim (EngineConfig.shared_pool):
    # the physical page axis carries the page sharding instead
    shared_specs = {
        "k_pages_g": P(None, None, pg, None, None),
        "v_pages_g": P(None, None, pg, None, None),
        "k_pages_w": P(None, None, pw, None, None),
        "v_pages_w": P(None, None, pw, None, None),
        "k_scale_g": P(None, None, pg),
        "v_scale_g": P(None, None, pg),
        "k_scale_w": P(None, None, pw),
        "v_scale_w": P(None, None, pw),
    }
    kw = {}
    for f in dataclasses.fields(cache):
        leaf = getattr(cache, f.name)
        if leaf is None:
            kw[f.name] = None
            continue
        spec = field_specs[f.name]
        if len(spec) != leaf.ndim:
            spec = shared_specs[f.name]
        kw[f.name] = NamedSharding(mesh, spec)
    return type(cache)(**kw)


# ---------------------------------------------------------------------------
# cell builders
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    arch: str
    shape: ShapeConfig
    kind: str
    jitted: Any                 # jit-wrapped step fn
    abstract_args: Tuple        # ShapeDtypeStructs to .lower(*args)
    chips: int
    note: str = ""
    fusible_last2: frozenset = frozenset()   # attention-intermediate dims


def _train_fusible_hints(cfg: ModelConfig, total_seq: int,
                         mesh: Mesh) -> frozenset:
    """Score/probability block dims for ring attention + dense paths."""
    m = mesh.shape["model"]
    hints = set()
    for s in {total_seq, total_seq // m}:
        for s2 in {total_seq, total_seq // m}:
            hints.add((s, s2))
    if cfg.is_encoder_decoder:
        enc = total_seq // 4
        for a in {enc, enc // m, total_seq, total_seq // m}:
            for b in {enc, enc // m, total_seq, total_seq // m}:
                hints.add((a, b))
    return frozenset(hints)


def _decode_fusible_hints(cfg: ModelConfig, acache, eng: EngineConfig,
                          mesh: Mesh, plan) -> frozenset:
    hints = set()
    T = eng.page_tokens
    dh, G = cfg.d_head, cfg.group_size
    pools = (("k_pages_g", plan.page_axes_g), ("k_pages_w",
                                               plan.page_axes_w))
    for name, axes in pools:
        pool = getattr(acache, name, None)
        if pool is None:
            continue
        shards = 1
        for a in axes:
            shards *= mesh.shape.get(a, 1)
        npl = pool.shape[3] // max(shards, 1)
        for np_ in {npl, pool.shape[3]}:
            hints |= {(np_, T), (np_ * T, dh), (dh, np_ * T),
                      (G, np_ * T), (np_ * T, G), (np_ * T, T)}
    if getattr(acache, "cross_k", None) is not None:
        senc = acache.cross_k.shape[2]
        npc = senc // T
        hints |= {(npc, T), (npc * T, dh), (dh, npc * T)}
    return frozenset(hints)


def build_train_cell(arch: str, mesh: Mesh, *, multi_pod: bool,
                     eng_overrides=None) -> Cell:
    cfg = get_config(arch)
    shape = SHAPES["train_4k"]
    rt = runtime_for(cfg, kind="train")
    fsdp = cfg.param_count() * 2 > FSDP_THRESHOLD * 2  # bf16 bytes
    overrides = None
    if fsdp and cfg.is_moe:
        # §Perf K3: for MoE, ZeRO-shard the per-expert FFN dim over `data`
        # instead of d_model — the expert einsums then contract over an
        # UNSHARDED dim and XLA reshards the (much smaller) activations
        # rather than gathering 2 TB of expert weights per pass
        overrides = {"embed": None, "moe_mlp": "data"}
    rules = shd.make_rules(fsdp=fsdp, multi_pod=multi_pod,
                           overrides=overrides)
    eng = engine_config_for(cfg, shape, eng_overrides)

    aparams, specs = transformer.abstract_params(cfg, jnp.bfloat16)
    params_sh = shd.tree_shardings(aparams, specs, rules, mesh)

    mdt = (jnp.bfloat16 if cfg.param_count() > BF16_MOMENTS_THRESHOLD
           else jnp.float32)
    acfg = opt_mod.AdamWConfig(moment_dtype=mdt)
    astate = TrainState(
        params=aparams,
        opt=opt_mod.AdamWState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            m=jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, mdt),
                           aparams),
            v=jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, mdt),
                           aparams)),
        ef=None)
    state_sh = TrainState(
        params=params_sh,
        opt=opt_mod.AdamWState(step=_sh(mesh), m=params_sh, v=params_sh),
        ef=None)

    abatch = input_specs(cfg, shape, rt)
    batch_sh = batch_shardings(cfg, shape, mesh, rules)

    layer_constrain = None
    if False and fsdp:  # §Perf K1/K2: REFUTED — constraint-steered ZeRO-3
        # gathers repartition the layer einsums replicated over `data`
        # (compute ×12–14 on dbrx/kimi); kept for the record/real-TPU retry
        # ZeRO-3: gather each layer's fsdp shards INSIDE the scan body
        # (per-slice all-gather fwd, per-slice reduce-scatter of grads in
        # bwd) — without this XLA moves full-stack collectives into the
        # loop (EXPERIMENTS.md §Perf, kimi-k2 iteration 1)
        tp_rules = shd.make_rules(fsdp=False, multi_pod=multi_pod)
        layer_specs = specs["layers"]
        batch_axes = tp_rules["batch"]
        x_sh = NamedSharding(mesh, P(
            batch_axes if len(batch_axes) > 1 else batch_axes[0],
            None, None))

        def layer_constrain(pl_, xc):
            def one(leaf, ax):
                if ax is None or not hasattr(leaf, "shape"):
                    return leaf
                ax = tuple(ax)[1:]  # strip the scanned "layer" axis
                return jax.lax.with_sharding_constraint(
                    leaf, NamedSharding(mesh, shd.spec_for_shape(
                        leaf.shape, ax, tp_rules, mesh)))
            is_leaf = lambda x: x is None or isinstance(x, tuple)  # noqa
            pl_ = jax.tree.map(one, pl_, layer_specs, is_leaf=is_leaf)
            # keep activations batch-sharded: gathered (data-replicated)
            # weights otherwise make XLA replicate the layer compute over
            # `data` (measured 14× flops on dbrx — §Perf iteration 2)
            xc = jax.lax.with_sharding_constraint(xc, x_sh)
            return pl_, xc

    step_fn = make_train_step(cfg, rt, acfg, eng,
                              layer_constrain=layer_constrain)
    jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    total_seq = shape.seq_len
    return Cell(arch, shape, "train", jitted, (astate, abatch), mesh.size,
                note=f"fsdp={fsdp} moments={mdt.__name__} remat={eng.remat}",
                fusible_last2=_train_fusible_hints(cfg, total_seq, mesh))


def build_prefill_cell(arch: str, mesh: Mesh, *, multi_pod: bool,
                       eng_overrides=None) -> Cell:
    cfg = get_config(arch)
    shape = SHAPES["prefill_32k"]
    rt = runtime_for(cfg)
    rules = shd.make_rules(fsdp=False, multi_pod=multi_pod)
    eng = engine_config_for(cfg, shape, eng_overrides)
    engine = KVNANDEngine(cfg, eng, rt, mesh)

    aparams, specs = transformer.abstract_params(cfg, jnp.bfloat16)
    if eng.quant != "none":
        aparams, specs = _abstract_quant(cfg, eng.quant)
    params_sh = shd.tree_shardings(aparams, specs, rules, mesh)

    abatch = input_specs(cfg, shape, rt)
    batch_sh = batch_shardings(cfg, shape, mesh, rules)
    max_ctx = shape.seq_len + 1

    def prefill_step(params, batch):
        return engine.prefill(params, batch, max_ctx)

    acache = engine.abstract_cache(
        shape.global_batch, max_ctx,
        enc_len=(shape.seq_len // rt.enc_frames_ratio
                 if cfg.is_encoder_decoder else 0))
    plan = engine.plan(shape.global_batch, max_ctx)
    cache_sh = cache_shardings(acache, mesh, plan)

    jitted = jax.jit(prefill_step, in_shardings=(params_sh, batch_sh),
                     out_shardings=(None, cache_sh))
    return Cell(arch, shape, "prefill", jitted, (aparams, abatch), mesh.size,
                note=f"variant={eng.variant}",
                fusible_last2=_train_fusible_hints(cfg, shape.seq_len, mesh))


def build_decode_cell(arch: str, shape_name: str, mesh: Mesh, *,
                      multi_pod: bool, eng_overrides=None) -> Cell:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    rt = runtime_for(cfg)
    rules = shd.make_rules(fsdp=False, multi_pod=multi_pod)
    eng = engine_config_for(cfg, shape, eng_overrides)
    engine = KVNANDEngine(cfg, eng, rt, mesh)

    aparams, specs = transformer.abstract_params(cfg, jnp.bfloat16)
    if eng.quant != "none":
        aparams, specs = _abstract_quant(cfg, eng.quant)
    params_sh = shd.tree_shardings(aparams, specs, rules, mesh)

    B, S_ctx = shape.global_batch, shape.seq_len
    enc_len = (S_ctx // rt.enc_frames_ratio if cfg.is_encoder_decoder else 0)
    acache = engine.abstract_cache(B, S_ctx + 8, enc_len=enc_len)
    NPg = (acache.k_pages_g.shape[3] if acache.k_pages_g is not None else 1)
    plan = plan_sharding(mesh, B, NPg)
    cache_sh = cache_shardings(acache, mesh, plan)
    tok_sh = NamedSharding(mesh, P(_axes_or_none(plan.batch_axes), None))
    atoks = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    def serve_step(params, cache, tokens):
        return engine.decode_step(params, cache, tokens)

    jitted = jax.jit(serve_step,
                     in_shardings=(params_sh, cache_sh, tok_sh),
                     out_shardings=(None, cache_sh), donate_argnums=(1,))
    return Cell(arch, shape, "decode", jitted, (aparams, acache, atoks),
                mesh.size,
                note=f"variant={eng.variant} quant={eng.quant} "
                     f"pages={plan.page_axes_g}",
                fusible_last2=_decode_fusible_hints(cfg, acache, eng, mesh,
                                                    plan))


def _abstract_quant(cfg: ModelConfig, scheme: str):
    holder = {}

    def f(k):
        params, specs = transformer.init_model(cfg, k, jnp.bfloat16)
        qp, qs = quantize_params_and_specs(params, specs, scheme)
        holder["specs"] = qs
        return qp

    aparams = jax.eval_shape(f, jax.random.PRNGKey(0))
    return aparams, holder["specs"]


def build_cell(arch: str, shape_name: str, mesh: Mesh, *, multi_pod: bool,
               eng_overrides=None) -> Optional[Cell]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None
    if shape.kind == "train":
        return build_train_cell(arch, mesh, multi_pod=multi_pod,
                                eng_overrides=eng_overrides)
    if shape.kind == "prefill":
        return build_prefill_cell(arch, mesh, multi_pod=multi_pod,
                                  eng_overrides=eng_overrides)
    return build_decode_cell(arch, shape_name, mesh, multi_pod=multi_pod,
                             eng_overrides=eng_overrides)
