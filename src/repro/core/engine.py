"""KVNAND engine — prefill + decode with paged KV, compact/discrete plans.

The decode step realizes the paper's Figure 7(b) on a TPU mesh:

  * every memory-bound GEMV (QKV gen, Logit, Attend, O-proj, FFN) runs where
    its bytes live — weights TP-sharded over `model`, KV pages sequence-
    striped over `model` (± spare batch axes for batch-1 long context);
  * Logit/Attend are per-shard partials over local pages, merged by a
    log-sum-exp combine (the paper's NPU softmax-aggregation, Fig 8 ❺–❼);
  * `variant="discrete"` pipelines head groups (Fig 9(c)/10(a)): the q-GEMV
    of head-group i+1 is issued in the same scan step as the attention of
    head-group i with no data dependence between them — XLA's latency-hiding
    scheduler overlaps them exactly as the G1/G2 dies do.  On a TPU the
    paper's *spatial* G1/G2 split would idle half the MXUs (flash PEs are
    fixed-function; TPUs are not), so the split is temporal — see DESIGN.md.
  * `variant="compact"` fuses all heads into single larger GEMVs (max TP,
    Fig 10(b)).

Memory discipline (§Perf iteration 1): KV pools and recurrent states are
scan CARRIES updated in place at a traced layer index — never scan xs/ys.
Threading pools through xs/ys made XLA rewrite the full per-layer pool
through the ys-stacking buffer every step (~70 MB of copy traffic per layer
against 4 KB of appended KV at qwen1.5-0.5b/decode_32k scale).

Layer heterogeneity (gemma3 5:1 local:global, hymba sparse-global) scans
over repeating layer *groups*; global/window pools are indexed by per-group
base offsets carried as scanned index arrays.  Models whose layers are not
all alike (leading dense-FFN layers before MoE ones) keep one param stack
per kind, each scanned in its own groups, in layer order (`LayerStack`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import EngineConfig, ModelConfig
from repro.core import paged_kv, seqpar
from repro.core.paged_kv import DecodeCache
from repro.kernels.paged_attention import (decode_walk_pages,
                                           paged_attention_partial)
from repro.models import attention as attn_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import dense, rms_norm
from repro.models.transformer import (Runtime, embed_inputs, embed_tokens,
                                      ffn, layer_stacks, lm_head_logits,
                                      post_norm)

STATE_LEAVES = ("rwkv_state", "rwkv_shift", "rwkv_shift2", "ssm_state",
                "conv_tail")
POOL_G = ("k_pages_g", "v_pages_g", "k_scale_g", "v_scale_g")
POOL_W = ("k_pages_w", "v_pages_w", "k_scale_w", "v_scale_w")


# ---------------------------------------------------------------------------
# Mesh planning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardPlan:
    batch_axes: Tuple[str, ...] = ()
    page_axes_g: Tuple[str, ...] = ()
    page_axes_w: Tuple[str, ...] = ()


def _axes_size(mesh: Optional[Mesh], axes) -> int:
    if mesh is None:
        return 1
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return max(n, 1)


def plan_sharding(mesh: Optional[Mesh], batch: int,
                  np_g_raw: int) -> ShardPlan:
    """Pick batch vs page mesh axes.  Batch-1 long context pushes spare
    data/pod axes onto the global page dimension (up to 512-way striping)."""
    if mesh is None or mesh.size == 1:
        return ShardPlan()
    batch_axes: List[str] = []
    spare: List[str] = []
    rem = batch
    for a in ("pod", "data"):
        if a not in mesh.shape:
            continue
        if rem % mesh.shape[a] == 0 and rem >= mesh.shape[a]:
            batch_axes.append(a)
            rem //= mesh.shape[a]
        else:
            spare.append(a)
    page_axes_g: List[str] = []
    n = mesh.shape["model"]
    for a in spare:
        if np_g_raw >= n * mesh.shape[a]:
            page_axes_g.append(a)
            n *= mesh.shape[a]
    page_axes_g.append("model")
    return ShardPlan(tuple(batch_axes), tuple(page_axes_g), ("model",))


# ---------------------------------------------------------------------------
# Layer stacks
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerStack:
    """One param stack (`params[name]`, a leading layer axis), run as a
    lax.scan over `n_groups` groups of `period` layers whose global/window
    pattern repeats.  Pool indices: a group's global (window) layers take
    the global (window) pool's next `g_per_group` (`w_per_group`)
    indices, `g_off`/`w_off` per position in the group."""
    name: str
    first: int                      # absolute index of its first layer
    n_groups: int
    pattern: Tuple[bool, ...]       # is_global per position in a group
    g0: int                         # pool indices of its first layer
    w0: int
    g_off: Tuple[int, ...]
    w_off: Tuple[int, ...]
    g_per_group: int
    w_per_group: int

    @property
    def period(self) -> int:
        return len(self.pattern)

    def bases(self) -> Dict[str, jax.Array]:
        """Per-group base indices (scanned): layer, global, window pool."""
        n = jnp.arange(self.n_groups, dtype=jnp.int32)
        return {k: n * step + start if start else n * step
                for k, start, step in (("l0", self.first, self.period),
                                       ("g0", self.g0, self.g_per_group),
                                       ("w0", self.w0, self.w_per_group))}


def layer_stacks_of(cfg: ModelConfig) -> Tuple[LayerStack, ...]:
    stacks = []
    g = w = 0
    for name, first, n in layer_stacks(cfg):
        period, pattern = paged_kv.layer_pattern(cfg, first, n)
        g_off, w_off = [], []
        gp = wp = 0
        for is_glob in pattern:
            g_off.append(gp)
            w_off.append(wp)
            if cfg.family != "ssm":
                if cfg.window is not None and not is_glob:
                    wp += 1
                else:
                    gp += 1
        stacks.append(LayerStack(name, first, n // period, pattern, g, w,
                                 tuple(g_off), tuple(w_off), gp, wp))
        g += gp * (n // period)
        w += wp * (n // period)
    return tuple(stacks)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class KVNANDEngine:
    def __init__(self, cfg: ModelConfig, eng: Optional[EngineConfig] = None,
                 rt: Optional[Runtime] = None, mesh: Optional[Mesh] = None):
        self.cfg = cfg
        self.eng = eng or EngineConfig()
        self.rt = rt or Runtime()
        self.mesh = mesh
        self.stacks = layer_stacks_of(cfg)

    # ------------------------------------------------------------------
    # cache construction
    # ------------------------------------------------------------------
    def plan(self, batch: int, max_context: int) -> ShardPlan:
        return plan_sharding(
            self.mesh, batch,
            paged_kv.ceil_div(max_context, self.eng.page_tokens))

    def _cache_kw(self, batch: int, max_context: int, enc_len: int):
        plan = self.plan(batch, max_context)
        return dict(dtype=jnp.dtype(self.eng.kv_dtype), enc_len=enc_len,
                    page_shards_g=_axes_size(self.mesh, plan.page_axes_g),
                    page_shards_w=_axes_size(self.mesh, plan.page_axes_w))

    def init_cache(self, batch: int, max_context: int,
                   enc_len: int = 0) -> DecodeCache:
        return paged_kv.init_cache(self.cfg, self.eng, batch, max_context,
                                   **self._cache_kw(batch, max_context,
                                                    enc_len))

    def abstract_cache(self, batch: int, max_context: int,
                       enc_len: int = 0) -> DecodeCache:
        return paged_kv.abstract_cache(self.cfg, self.eng, batch, max_context,
                                       **self._cache_kw(batch, max_context,
                                                        enc_len))

    def decode_page_visits(self, cache: DecodeCache, pool: str = "g",
                           lengths=None) -> int:
        """Pages one step's paged attention reads per layer of the global
        (`pool="g"`) or window (`"w"`) pool; 0 for archs with no such
        pool.  With `lengths` — every row's keys in a decode step's
        kernel walk, 0 for a row the step leaves alone — the pages of the
        blocks the Pallas kernel fetches (`decode_walk_pages`).  Without,
        rows × pages per row — the page-table width of a shared pool, the
        stripe's (ring's) page count otherwise — which a verify step's
        jnp walk reads."""
        pages = cache.k_pages_g if pool == "g" else cache.k_pages_w
        if pages is None:
            return 0
        shared = self.eng.shared_pool
        if shared:
            table = cache.page_table_g if pool == "g" else cache.page_table_w
            NP = table.shape[1]
        else:
            NP = pages.shape[3]
        if lengths is None:
            return cache.lengths.shape[0] * NP
        return decode_walk_pages(lengths, self.eng.page_tokens, NP,
                                 shared=shared,
                                 partitions=self.eng.attn_partitions)

    def _walk(self, params, body: Callable, carry, extra=None):
        """Every layer in order: ``body(carry, pl_, l, g, w, is_glob, xj)
        -> (carry, y)`` with the layer's params `pl_` (None when `params`
        is None), its layer / global-pool / window-pool indices (traced)
        and whether it is global (static).  One lax.scan over groups per
        stack.  `extra`: the per-stack ys of an earlier walk, `xj` the
        layer's entry.  Returns (carry, per-stack ys)."""
        out = []
        for si, st in enumerate(self.stacks):
            xs = st.bases()
            if params is not None:
                xs["p"] = jax.tree.map(
                    lambda a, st=st: a.reshape((st.n_groups, st.period)
                                               + a.shape[1:]),
                    params[st.name])
            if extra is not None:
                xs["x"] = extra[si]

            def group_body(carry, xs, st=st):
                ys = []
                for j, is_glob in enumerate(st.pattern):
                    pick = lambda a, j=j: a[j]   # noqa: E731
                    pl_ = (jax.tree.map(pick, xs["p"]) if "p" in xs
                           else None)
                    xj = jax.tree.map(pick, xs["x"]) if "x" in xs else None
                    carry, y = body(carry, pl_, xs["l0"] + j,
                                    xs["g0"] + st.g_off[j],
                                    xs["w0"] + st.w_off[j], is_glob, xj)
                    ys.append(y)
                if all(y is None for y in ys):
                    return carry, None
                return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)

            carry, ys = jax.lax.scan(group_body, carry, xs)
            out.append(ys)
        return carry, out

    def _ffn_half(self, pl_, x, rows=None):
        """x + FFN(norm(x)) (post-normed where the layer has it); returns
        (x, held pairs of an MoE layer or None)."""
        cfg = self.cfg
        h = rms_norm(x, pl_["ln2"], cfg.norm_eps)
        with jax.named_scope("mlp"):
            ff, held = ffn(pl_, cfg, h, rows=rows)
        return x + post_norm(pl_, "ln2_post", cfg, ff), held

    # ------------------------------------------------------------------
    # paged attention dispatch (single device vs sharded combine)
    # ------------------------------------------------------------------
    def _paged_attn(self, q, kp, vp, base, length, plan: ShardPlan,
                    pool: str, window, ks=None, vs=None, table=None,
                    layer=None):
        """ks/vs: per-page×head dequant scales (None -> bf16 pool).

        kp/vp with a batch dim ([B, K, NP, T, dh]) read the slot's private
        stripe; 4-D pools ([K, P_total, T, dh]) are the SHARED pool and
        `table` [B, NP] supplies the logical→physical walk.  With `layer`
        (single device only) they are the stacked pools, one dim more,
        read at that layer in place.
        """
        kv_quant = self.eng.kv_quant if ks is not None else "none"
        page_axes = plan.page_axes_g if pool == "g" else plan.page_axes_w
        shared = kp.ndim == (4 if layer is None else 5)
        if self.mesh is None or self.mesh.size == 1 or not page_axes:
            o, _, _ = paged_attention_partial(
                q, kp, vp, base, length, window=window,
                impl=self.eng.attn_impl, kv_quant=kv_quant,
                k_scale=ks, v_scale=vs,
                page_table=table if shared else None,
                partitions=self.eng.attn_partitions, layer=layer)
            return o
        if shared:
            return seqpar.paged_decode_attention_sharded_shared(
                q, kp, vp, table, base, length, self.mesh, window=window,
                batch_axes=plan.batch_axes, page_axes=page_axes,
                impl=self.eng.attn_impl, kv_quant=kv_quant,
                k_scale=ks, v_scale=vs,
                partitions=self.eng.attn_partitions)
        return seqpar.paged_decode_attention_sharded(
            q, kp, vp, base, length, self.mesh, window=window,
            batch_axes=plan.batch_axes, page_axes=page_axes,
            impl=self.eng.attn_impl, kv_quant=kv_quant,
            k_scale=ks, v_scale=vs,
            partitions=self.eng.attn_partitions)

    # ------------------------------------------------------------------
    # in-place pool ops (pools carried through the layer scan)
    # ------------------------------------------------------------------
    def _append_token(self, pool, layer, phys, slot, val):
        """pool: [L, B, K, NP, T, dh]; write one token's K or V in place
        through the `paged_kv` writer family (KV004: pool-leaf writes live
        in core/paged_kv.py; see its docstring for the uniform-lengths
        fast-path rationale)."""
        return paged_kv.append_token_inplace(
            pool, layer, phys, slot, val,
            uniform_lengths=self.eng.uniform_lengths)

    @staticmethod
    def _layer_slice(pool, layer):
        return jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)

    def _global_bases(self, table) -> jax.Array:
        """Per-page base positions [B, NP] for attention over the global
        pool (decode and verify share this).  Shared pools walk LOGICAL
        pages through the table, so logical page j's base is simply j·T
        and pages past `lengths` (unallocated table entries) are
        data-invalid already; stripe tables are permutations within the
        stripe, inverted here into physical-page-indexed bases."""
        B, NP = table.shape
        T = self.eng.page_tokens
        if self.eng.shared_pool:
            return jnp.broadcast_to(
                (jnp.arange(NP, dtype=jnp.int32) * T)[None], (B, NP))
        return jnp.zeros((B, NP), jnp.int32).at[
            jnp.arange(B)[:, None], table].set(
            jnp.arange(NP, dtype=jnp.int32)[None] * T)

    def _kv_lengths(self, lengths):
        """Keys each row's decode attention reads: its context and the
        token just appended — none for a row the step leaves alone
        (`active`), whose output is ignored, so the kernel fetches one
        block for it instead of its stale pages."""
        kv = lengths + 1
        return kv if self._active is None else jnp.where(self._active, kv,
                                                         0)

    # ------------------------------------------------------------------
    # per-layer attention (compact vs discrete)
    # ------------------------------------------------------------------
    def _attend_compact(self, pl_, x_norm, kp, vp, ks, vs, base, lengths,
                        plan, pool, window, table=None, layer=None,
                        rope=True):
        """Fused QKV gen + attention (KVNAND-C, Fig 10b).  kp/vp are the
        already-appended layer slices (+scales when the pool is quantized),
        or the stacked pools and their `layer`."""
        with jax.named_scope("qkv"):
            q, _, _ = attn_mod.project_qkv(pl_["attn"], self.cfg, x_norm,
                                           lengths[:, None], rope=rope)
        with jax.named_scope("paged_attn"):
            return self._paged_attn(q[:, 0], kp, vp, base,
                                    self._kv_lengths(lengths), plan, pool,
                                    window, ks, vs, table, layer)

    def _attend_discrete(self, pl_, x_norm, kp, vp, ks, vs, base, lengths,
                         plan, pool, window, table=None, rope=True):
        """Head-group pipelined attention (KVNAND-D, Fig 10a): q-GEMV of
        group i+1 is independent of group i's attention -> overlapped."""
        cfg = self.cfg
        B = x_norm.shape[0]
        K = cfg.n_kv_heads
        x_tok = x_norm[:, 0]
        k_axis = 0 if kp.ndim == 4 else 1   # shared pools are [K, P, T, dh]

        def body(q_cur, i):
            with jax.named_scope("qkv"):
                q_next = attn_mod.project_q_group(
                    pl_["attn"], cfg, x_tok, jnp.minimum(i + 1, K - 1),
                    lengths, rope=rope)
            # slice head group i on the K dim directly (no pool transpose)
            with jax.named_scope("pool_view"):
                kp_i = jax.lax.dynamic_slice_in_dim(kp, i, 1, k_axis)
                vp_i = jax.lax.dynamic_slice_in_dim(vp, i, 1, k_axis)
                ks_i = vs_i = None
                if ks is not None:
                    ks_i = jax.lax.dynamic_slice_in_dim(ks, i, 1, k_axis)
                    vs_i = jax.lax.dynamic_slice_in_dim(vs, i, 1, k_axis)
            with jax.named_scope("paged_attn"):
                o = self._paged_attn(q_cur, kp_i, vp_i, base,
                                     self._kv_lengths(lengths), plan, pool,
                                     window, ks_i, vs_i, table)  # [B, G, dh]
            return q_next, o

        with jax.named_scope("qkv"):
            q0 = attn_mod.project_q_group(pl_["attn"], cfg, x_tok,
                                          jnp.zeros((), jnp.int32), lengths,
                                          rope=rope)
        _, outs = jax.lax.scan(body, q0, jnp.arange(K))
        return outs.transpose(1, 0, 2, 3).reshape(B, cfg.n_heads,
                                                  cfg.d_head)

    # ------------------------------------------------------------------
    # decode blocks
    # ------------------------------------------------------------------
    def _decode_attn_layer(self, pl_, x, pools, g_idx, w_idx, lengths,
                           plan, is_glob):
        cfg = self.cfg
        shared = self.eng.shared_pool
        h = rms_norm(x, pl_["ln1"], cfg.norm_eps)
        use_window = (cfg.window is not None) and not is_glob
        rope = attn_mod.use_rope(cfg, is_glob)
        # K/V for the new token (the paper's ❸→❹ write into G2/own pages)
        with jax.named_scope("qkv"):
            _, k_new, v_new = attn_mod.project_qkv(pl_["attn"], cfg, h,
                                                   lengths[:, None],
                                                   rope=rope)
        k1, v1 = k_new[:, 0], v_new[:, 0]
        T = self.eng.page_tokens
        slot = lengths % T
        if use_window:
            kname, vname, idx = "k_pages_w", "v_pages_w", w_idx
            if shared:
                NPw = self._table_w.shape[1]
                ring = (lengths // T) % NPw
                phys = jnp.take_along_axis(self._table_w, ring[:, None],
                                           axis=1)[:, 0]
                table, drop = self._table_w, pools[kname].shape[2]
            else:
                NP = pools[kname].shape[3]
                phys = (lengths // T) % NP
                table, drop = None, NP
            base, window = self._page_pos_w_new, cfg.window
        else:
            kname, vname, idx = "k_pages_g", "v_pages_g", g_idx
            logical = lengths // T
            phys = jnp.take_along_axis(self._table, logical[:, None],
                                       axis=1)[:, 0]
            table = self._table if shared else None
            drop = pools[kname].shape[2 if shared else 3]
            base, window = self._base_g, None
        if self._active is not None:
            # interleaved scheduler: slots mid-prefill (or empty) must not
            # append — redirect their page index out of range so the
            # writer discards the write
            phys = jnp.where(self._active, phys, drop)
        page_axes = (plan.page_axes_w if use_window else plan.page_axes_g)
        sharded = (self.mesh is not None and self.mesh.size > 1
                   and bool(page_axes))
        fmt = self.eng.kv_quant
        ksname = "k_scale_w" if use_window else "k_scale_g"
        vsname = "v_scale_w" if use_window else "v_scale_g"
        with jax.named_scope("kv_append"):
            if sharded and shared:
                # shared pool sharded over P_total: the owning shard
                # translates the global physical index to its local range
                # and scatters
                out = seqpar.sharded_append_shared(
                    pools[kname], pools[vname], idx, k1, v1, phys, slot,
                    self.mesh, batch_axes=plan.batch_axes,
                    page_axes=page_axes, k_scale=pools.get(ksname),
                    v_scale=pools.get(vsname), kv_quant=fmt)
                if fmt != "none":
                    (pools[kname], pools[vname], pools[ksname],
                     pools[vsname]) = out
                else:
                    pools[kname], pools[vname] = out
            elif sharded and self.eng.uniform_lengths:
                # append INSIDE the owning shard (paper: direct G2-die
                # write); a pjit-level update on the sharded page dim
                # lowers to a full-pool ownership select per layer (§Perf
                # iteration 2)
                if fmt != "none":
                    (pools[kname], pools[vname], pools[ksname],
                     pools[vsname]) = seqpar.sharded_append_uniform(
                        pools[kname], pools[vname], idx, k1, v1, phys,
                        slot, self.mesh, batch_axes=plan.batch_axes,
                        page_axes=page_axes, k_scale=pools[ksname],
                        v_scale=pools[vsname], kv_quant=fmt)
                else:
                    (pools[kname],
                     pools[vname]) = seqpar.sharded_append_uniform(
                        pools[kname], pools[vname], idx, k1, v1, phys,
                        slot, self.mesh, batch_axes=plan.batch_axes,
                        page_axes=page_axes)
            elif fmt != "none":
                # page-granular requantizing append (tentpole write path)
                if shared:
                    append = paged_kv.append_token_quant_shared
                else:
                    append = (paged_kv.append_token_quant_uniform
                              if self.eng.uniform_lengths
                              else paged_kv.append_token_quant)
                pools[kname], pools[ksname] = append(
                    pools[kname], pools[ksname], idx, phys, slot, k1, fmt)
                pools[vname], pools[vsname] = append(
                    pools[vname], pools[vsname], idx, phys, slot, v1, fmt)
            elif shared:
                pools[kname] = paged_kv.append_global_shared(
                    pools[kname], idx, phys, slot, k1)
                pools[vname] = paged_kv.append_global_shared(
                    pools[vname], idx, phys, slot, v1)
            else:
                pools[kname] = self._append_token(pools[kname], idx, phys,
                                                  slot, k1)
                pools[vname] = self._append_token(pools[vname], idx, phys,
                                                  slot, v1)
        pool = "w" if use_window else "g"
        if (fmt == "none" and (self.mesh is None or self.mesh.size == 1)
                and self.eng.variant != "discrete"
                and not self.eng.hg_pipeline):
            # the kernel reads this layer of the stacked pools in place:
            # a layer slice would be a copy of it, in every layer
            o = self._attend_compact(pl_, h, pools[kname], pools[vname],
                                     None, None, base, lengths, plan, pool,
                                     window, table, layer=idx, rope=rope)
        else:
            with jax.named_scope("pool_view"):
                kp = self._layer_slice(pools[kname], idx)
                vp = self._layer_slice(pools[vname], idx)
                ks = vs = None
                if fmt != "none":
                    ks = self._layer_slice(pools[ksname], idx)
                    vs = self._layer_slice(pools[vsname], idx)
            attend = (self._attend_discrete
                      if self.eng.variant == "discrete"
                      or self.eng.hg_pipeline else self._attend_compact)
            o = attend(pl_, h, kp, vp, ks, vs, base, lengths, plan, pool,
                       window, table, rope=rope)
        with jax.named_scope("attn_out"):
            aout = attn_mod.project_out(pl_["attn"], cfg, o[:, None], h)
        return h, aout, pools

    def _decode_block(self, pl_, x, pools, states, cross, l_idx, g_idx,
                      w_idx, lengths, plan, is_glob):
        """One layer of the decode step: returns ((x, states), pools,
        held pairs of an MoE layer or None)."""
        cfg = self.cfg

        if cfg.family == "ssm":
            return self._rwkv_decode_block(pl_, x, states, l_idx), pools, None

        h, aout, pools = self._decode_attn_layer(
            pl_, x, pools, g_idx, w_idx, lengths, plan, is_glob)

        if cfg.family == "hybrid":
            st = {k: self._layer_slice(states[k], l_idx)
                  for k in ("ssm_state", "conv_tail")}
            sout, s_new, tail_new = ssm_mod.ssm_decode_step(
                pl_["ssm"], cfg, h, st["ssm_state"], st["conv_tail"])
            aout = (aout + sout) * 0.5
            s_new, tail_new = self._mask_state(
                (s_new, st["ssm_state"]), (tail_new, st["conv_tail"]))
            states["ssm_state"] = states["ssm_state"].at[l_idx].set(s_new)
            states["conv_tail"] = states["conv_tail"].at[l_idx].set(
                tail_new.astype(states["conv_tail"].dtype))
        x = x + post_norm(pl_, "ln1_post", cfg, aout)

        if cross is not None:
            h = rms_norm(x, pl_["ln_cross"], cfg.norm_eps)
            ck = self._layer_slice(cross["cross_k"], l_idx)
            cv = self._layer_slice(cross["cross_v"], l_idx)
            x = x + self._cross_attention(pl_["cross"], h, ck, cv, plan)

        x, held = self._ffn_half(pl_, x, rows=self._active)
        return (x, states), pools, held

    def _mask_state(self, *pairs):
        """Freeze recurrent-state updates for inactive slots: each pair is
        (new, old) with a leading batch dim; returns the masked news."""
        if self._active is None:
            return [new for new, _ in pairs] if len(pairs) > 1 else pairs[0][0]
        out = []
        for new, old in pairs:
            act = self._active.reshape((-1,) + (1,) * (new.ndim - 1))
            out.append(jnp.where(act, new, old.astype(new.dtype)))
        return out if len(pairs) > 1 else out[0]

    def _rwkv_decode_block(self, pl_, x, states, l_idx):
        cfg = self.cfg
        h = rms_norm(x, pl_["ln1"], cfg.norm_eps)
        st = self._layer_slice(states["rwkv_state"], l_idx)
        sh = self._layer_slice(states["rwkv_shift"], l_idx)
        tout, s_new, shift_new = rwkv_mod.rwkv_timemix(
            pl_["tmix"], cfg, h, st, sh.astype(h.dtype), chunked=False)
        x = x + tout
        h = rms_norm(x, pl_["ln2"], cfg.norm_eps)
        cm = pl_["cmix"]
        h_prev = self._layer_slice(states["rwkv_shift2"],
                                   l_idx).astype(h.dtype)[:, None]
        xk = h + (h_prev - h) * cm["mu_k"].astype(h.dtype)
        xr = h + (h_prev - h) * cm["mu_r"].astype(h.dtype)
        k = jnp.square(jax.nn.relu(dense(cm, "ck", xk)))
        v = dense(cm, "cv", k)
        r = jax.nn.sigmoid(dense(cm, "cr", xr))
        x = x + r * v
        s_new, shift_new, shift2_new = self._mask_state(
            (s_new, st), (shift_new, sh),
            (h[:, -1], self._layer_slice(states["rwkv_shift2"], l_idx)))
        states["rwkv_state"] = states["rwkv_state"].at[l_idx].set(s_new)
        states["rwkv_shift"] = states["rwkv_shift"].at[l_idx].set(
            shift_new.astype(states["rwkv_shift"].dtype))
        states["rwkv_shift2"] = states["rwkv_shift2"].at[l_idx].set(
            shift2_new.astype(states["rwkv_shift2"].dtype))
        return x, states

    def _cross_attention(self, pcross, h, ck, cv, plan: ShardPlan):
        """Whisper decode cross-attention via the paged partial-attention op
        (encoder KV viewed as pages: Senc = NP·T)."""
        cfg = self.cfg
        B = h.shape[0]
        Senc = ck.shape[1]
        T = self.eng.page_tokens
        NP = paged_kv.ceil_div(Senc, T)
        q = attn_mod._proj(pcross, "wq", h).reshape(
            B, cfg.n_heads, cfg.d_head)
        kp = ck.reshape(B, NP, T, cfg.n_kv_heads, cfg.d_head
                        ).transpose(0, 3, 1, 2, 4)
        vp = cv.reshape(B, NP, T, cfg.n_kv_heads, cfg.d_head
                        ).transpose(0, 3, 1, 2, 4)
        base = jnp.broadcast_to(
            (jnp.arange(NP, dtype=jnp.int32) * T)[None], (B, NP))
        length = jnp.full((B,), Senc, jnp.int32)
        o = self._paged_attn(q, kp, vp, base, length, plan, "w", None)
        return attn_mod.project_out(pcross, cfg, o[:, None])

    # ------------------------------------------------------------------
    # decode step
    # ------------------------------------------------------------------
    def _collect(self, cache: DecodeCache, names) -> Dict[str, jax.Array]:
        return {n: getattr(cache, n) for n in names
                if getattr(cache, n) is not None}

    def decode_step(self, params, cache: DecodeCache, tokens: jax.Array,
                    active: Optional[jax.Array] = None, *,
                    route_counts: bool = False):
        """tokens: [B, 1] -> (logits [B, V], updated cache).

        active: optional [B] bool mask (interleaved continuous batching):
        inactive slots — empty, or mid-way through a chunked prefill — get
        no KV append, no length advance, and frozen recurrent state, so a
        decode step never perturbs a stripe another path is filling.  Their
        logits are computed (the batch is dense) and ignored by the host.

        route_counts: also return the token-expert pairs of the active
        rows that landed on held experts, summed over the MoE layers
        (an int32 scalar; 0 for a model without them).
        """
        cfg, rt = self.cfg, self.rt
        if active is not None and self.eng.uniform_lengths:
            raise ValueError("active-mask decode requires the ragged "
                             "(uniform_lengths=False) append path")
        self._active = active
        B = tokens.shape[0]
        lengths = cache.lengths
        shared = self.eng.shared_pool
        plan = plan_sharding(
            self.mesh, B, paged_kv.pool_page_count(cache.k_pages_g, shared))

        # shared per-step page bookkeeping (identical for every layer)
        self._table = cache.page_table_g
        self._table_w = cache.page_table_w
        self._base_g = (self._global_bases(cache.page_table_g)
                        if cache.page_table_g is not None else None)
        if cache.page_pos_w is not None:
            T = self.eng.page_tokens
            NPw = cache.page_pos_w.shape[1]
            phys = (lengths // T) % NPw
            slot = lengths % T
            newp = cache.page_pos_w.at[jnp.arange(B), phys].set(
                lengths - slot)
            fresh = (slot == 0)
            if active is not None:
                fresh = fresh & active
            self._page_pos_w_new = jnp.where(
                fresh[:, None], newp, cache.page_pos_w)
        else:
            self._page_pos_w_new = None

        x = embed_tokens(params, cfg, tokens, rt)
        pools = self._collect(cache, POOL_G + POOL_W)
        states = self._collect(cache, STATE_LEAVES)
        cross = self._collect(cache, ("cross_k", "cross_v")) or None

        def layer(carry, pl_, l_idx, g_idx, w_idx, is_glob, _):
            xc, pools, states = carry
            (xc, states), pools, held = self._decode_block(
                pl_, xc, pools, states, cross, l_idx, g_idx, w_idx,
                lengths, plan, is_glob)
            return (xc, pools, states), held

        (x, pools, states), held = self._walk(params, layer,
                                              (x, pools, states))

        updates: Dict[str, Any] = dict(pools)
        updates.update(states)
        if self._page_pos_w_new is not None:
            updates["page_pos_w"] = self._page_pos_w_new
        updates["lengths"] = (lengths + 1 if active is None
                              else lengths + active.astype(lengths.dtype))
        new_cache = dataclasses.replace(cache, **updates)
        with jax.named_scope("logits"):
            logits = lm_head_logits(params, cfg, x)[:, 0]
        if route_counts:
            n_held = sum((jnp.sum(h) for h in jax.tree.leaves(held)),
                         jnp.zeros((), jnp.int32))
            return logits, new_cache, n_held
        return logits, new_cache

    # ------------------------------------------------------------------
    # speculative decode: draft-and-verify over a k+1-token span
    # ------------------------------------------------------------------
    def verify_step(self, params, cache: DecodeCache, tokens: jax.Array,
                    *, accept, active: Optional[jax.Array] = None):
        """Score a drafted span in ONE forward pass and append only the
        accepted prefix (DESIGN.md §11).

        tokens: [B, S] — per slot, the last emitted token followed by
        S-1 drafted tokens (prompt lookup, `serving/draft.py`); logits
        at span position j are the target distribution of the token
        AFTER tokens[:, j].  The span attends via the two-partial merge
        of chunked prefill (§8): a causal in-span partial over the
        span's fresh K/V (`seqpar._attn_block_partial` — the mask is
        position-relative, so one call serves every slot whatever its
        length) and a past-pages partial (`paged_chunk_attention`,
        batched per-row start/q_pos), merged by log-sum-exp.

        accept: traced callback ``logits [B, S, V] -> (n_acc [B], aux)``
        — the scheduler's sampler closure (`speculative_accept`), kept
        outside the engine so it stays sampling-free.  After it returns,
        ``n_acc[b] + 1`` span tokens (the last emitted token's K/V plus
        the accepted drafts) are appended per active slot through the
        span writers (`paged_kv.append_span*`): rejected positions are
        gated to the drop sentinel, so rollback is "never written" on
        every layout — f32, requantizing kv8/kv4 chains, window rings,
        and shared-pool tables alike.  `lengths` advance by the emitted
        count; the correction/bonus token's K/V lands on the NEXT step,
        exactly like sequential decode.

        active: optional [B] bool mask (continuous batching): inactive
        slots get no append and no length advance.

        Returns (aux, updated cache).  Recurrent families (ssm/hybrid)
        and encoder-decoder archs are unsupported (carried state cannot
        roll back); sharded meshes take the sequential decode path.
        """
        cfg, rt = self.cfg, self.rt
        if cfg.family in ("ssm", "hybrid"):
            raise ValueError(
                f"{cfg.family}: speculative verification cannot roll back "
                "carried recurrent state; decode sequentially")
        if cfg.is_encoder_decoder:
            raise ValueError("verify_step does not support encoder-decoder "
                             "archs")
        if self.mesh is not None and self.mesh.size > 1:
            raise NotImplementedError(
                "sharded verify_step is not wired; run speculation "
                "single-host (the mesh path covers sequential decode)")
        if self.eng.uniform_lengths:
            raise ValueError("verify_step requires the ragged "
                             "(uniform_lengths=False) append path: slots "
                             "accept different span lengths")
        B, S = tokens.shape
        lengths = cache.lengths
        shared = self.eng.shared_pool
        T = self.eng.page_tokens
        scale = cfg.d_head ** -0.5

        self._table = cache.page_table_g
        self._table_w = cache.page_table_w
        base_g = (self._global_bases(cache.page_table_g)
                  if cache.page_table_g is not None else None)

        positions = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
        x = embed_tokens(params, cfg, tokens, rt)
        pools = self._collect(cache, POOL_G + POOL_W)
        fmt = self.eng.kv_quant

        def attn_layer(xc, pl_, l_idx, g_idx, w_idx, is_glob, _):
            """One layer of the span forward; returns the layer output
            and the span's fresh (k, v) for the append phase."""
            use_window = (cfg.window is not None) and not is_glob
            window = cfg.window if use_window else None
            h = rms_norm(xc, pl_["ln1"], cfg.norm_eps)
            with jax.named_scope("qkv"):
                q, k, v = attn_mod.project_qkv(
                    pl_["attn"], cfg, h, positions,
                    rope=attn_mod.use_rope(cfg, is_glob))
            # in-span causal partial: the mask is position-RELATIVE
            # (span token i sees span tokens <= i, window likewise), so
            # relative coordinates serve every slot at once.  The span's
            # K/V are rounded through the pool dtype first — sequential
            # decode would read these tokens back from the pool, and the
            # greedy-parity guarantee needs the same values on both
            # paths (quantized pools keep full-precision span K/V: the
            # sequential requant chain is unknowable mid-span, and the
            # residual is bounded by the format's own quant noise).
            if fmt == "none":
                kv_dt = jnp.dtype(self.eng.kv_dtype)
                q_in = (q.astype(jnp.float32) * scale).astype(kv_dt)
                k_in, v_in, sc = k.astype(kv_dt), v.astype(kv_dt), 1.0
            else:
                q_in, k_in, v_in, sc = q, k, v, scale
            # past partial vs the slot's already-written pages
            if use_window:
                kname, vname, idx_l = "k_pages_w", "v_pages_w", w_idx
                base, table = cache.page_pos_w, self._table_w
            else:
                kname, vname, idx_l = "k_pages_g", "v_pages_g", g_idx
                base, table = base_g, self._table
            with jax.named_scope("pool_view"):
                kp = self._layer_slice(pools[kname], idx_l)
                vp = self._layer_slice(pools[vname], idx_l)
                ks = vs = None
                if fmt != "none":
                    sfx = "w" if use_window else "g"
                    ks = self._layer_slice(pools[f"k_scale_{sfx}"], idx_l)
                    vs = self._layer_slice(pools[f"v_scale_{sfx}"], idx_l)
            from repro.kernels.paged_attention import paged_chunk_attention
            with jax.named_scope("paged_attn"):
                o, m, l = seqpar._attn_block_partial(
                    q_in, k_in, v_in, jnp.arange(S),
                    jnp.zeros((), jnp.int32), causal=True, window=window,
                    is_global=None, scale=sc)
                o2, m2, l2 = paged_chunk_attention(
                    q, kp, vp, base, lengths, positions, window=window,
                    impl=self.eng.attn_impl, kv_quant=fmt, k_scale=ks,
                    v_scale=vs, page_table=table if shared else None,
                    partitions=self.eng.attn_partitions)
                o, m, l = seqpar.merge_two(o, m, l, o2, m2, l2)
            with jax.named_scope("attn_out"):
                aout = attn_mod.project_out(pl_["attn"], cfg,
                                            o.astype(h.dtype), h)
            xc = xc + post_norm(pl_, "ln1_post", cfg, aout)
            xc, _ = self._ffn_half(pl_, xc)
            # span K/V ride the ys stack — tiny ([period, B, S, K, dh])
            # next to the pool carries the memory discipline protects
            return xc, {"k": k, "v": v}

        x, span_kv = self._walk(params, attn_layer, x)
        with jax.named_scope("logits"):
            logits = lm_head_logits(params, cfg, x)        # [B, S, V]

        n_acc, aux = accept(logits)
        n_write = jnp.clip(jnp.asarray(n_acc, jnp.int32) + 1, 0, S)
        if active is not None:
            n_write = jnp.where(active, n_write, 0)

        # span page coordinates, shared by every layer of a pool: the
        # write gate redirects rejected/inactive positions to the drop
        # sentinel — rejected drafts never touch a page (the rollback)
        pos_s = lengths[None, :] + jnp.arange(S, dtype=jnp.int32)[:, None]
        slot_s = pos_s % T                                  # [S, B]
        write = jnp.arange(S, dtype=jnp.int32)[:, None] < n_write[None, :]
        phys_g = phys_w = None
        if cache.page_table_g is not None:
            drop_g = paged_kv.pool_page_count(cache.k_pages_g, shared)
            pg = jnp.take_along_axis(cache.page_table_g,
                                     (pos_s // T).T, axis=1).T
            phys_g = jnp.where(write, pg, drop_g)
        if cache.page_pos_w is not None:
            NPw = cache.page_pos_w.shape[1]
            ring = (pos_s // T) % NPw
            if shared:
                drop_w = paged_kv.pool_page_count(cache.k_pages_w, shared)
                pw = jnp.take_along_axis(cache.page_table_w, ring.T,
                                         axis=1).T
            else:
                drop_w, pw = NPw, ring
            phys_w = jnp.where(write, pw, drop_w)

        def append_layer(pools, _, l_idx, g_idx, w_idx, is_glob, kv):
            use_window = (cfg.window is not None) and not is_glob
            k_span, v_span = kv["k"], kv["v"]              # [B, S, K, dh]
            if use_window:
                idx_l, phys = w_idx, phys_w
                names = ("k_pages_w", "v_pages_w", "k_scale_w",
                         "v_scale_w")
            else:
                idx_l, phys = g_idx, phys_g
                names = ("k_pages_g", "v_pages_g", "k_scale_g",
                         "v_scale_g")
            kname, vname, ksname, vsname = names
            if fmt != "none":
                append = (paged_kv.append_span_quant_shared if shared
                          else paged_kv.append_span_quant)
                pools[kname], pools[ksname] = append(
                    pools[kname], pools[ksname], idx_l, phys, slot_s,
                    k_span, fmt)
                pools[vname], pools[vsname] = append(
                    pools[vname], pools[vsname], idx_l, phys, slot_s,
                    v_span, fmt)
            elif shared:
                pools[kname] = paged_kv.append_span_shared(
                    pools[kname], idx_l, phys, slot_s, k_span)
                pools[vname] = paged_kv.append_span_shared(
                    pools[vname], idx_l, phys, slot_s, v_span)
            else:
                pools[kname] = paged_kv.append_span(
                    pools[kname], idx_l, phys, slot_s, k_span)
                pools[vname] = paged_kv.append_span(
                    pools[vname], idx_l, phys, slot_s, v_span)
            return pools, None

        with jax.named_scope("kv_append"):
            pools, _ = self._walk(None, append_layer, pools, extra=span_kv)

        updates: Dict[str, Any] = dict(pools)
        if cache.page_pos_w is not None:
            # ring bases advance only for pages that received an
            # ACCEPTED token, replaying sequential decode's fresh-page
            # rule position by position
            NPw = cache.page_pos_w.shape[1]
            pos_w = cache.page_pos_w
            b_idx = jnp.arange(B)
            for s in range(S):
                ring = (pos_s[s] // T) % NPw
                fresh = (slot_s[s] == 0) & write[s]
                newp = pos_w.at[b_idx, ring].set(pos_s[s])
                pos_w = jnp.where(fresh[:, None], newp, pos_w)
            updates["page_pos_w"] = pos_w
        updates["lengths"] = lengths + n_write
        return aux, dataclasses.replace(cache, **updates)

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def prefill(self, params, batch: Dict[str, jax.Array], max_context: int,
                prompt_len: Optional[jax.Array] = None):
        """Full-prompt prefill.  Returns (last-token logits, primed cache).

        Attention runs compute-bound (ring/flash — the paper's NPU prefill);
        the K/V stream is page-packed into the pools (Fig 7a).

        prompt_len: traced scalar count of VALID tokens in batch["tokens"]
        (uniform across the batch).  When given, the trailing tokens are
        bucket padding (scheduler recompile avoidance): logits are gathered
        at the true last token, `lengths` reflect the true length, and the
        window-ring fill walks only real source pages so padding never
        evicts live KV.  Unsupported for recurrent state (ssm/hybrid),
        where padded tokens would pollute the carried state.
        """
        cfg, rt = self.cfg, self.rt
        if prompt_len is not None and cfg.family in ("ssm", "hybrid"):
            raise ValueError(
                f"{cfg.family}: bucketed prefill would fold padding into "
                "recurrent state; pass exact-length prompts instead")
        if prompt_len is not None and self.mesh is not None \
                and self.mesh.size > 1:
            raise ValueError("bucketed prefill is a single-host scheduler "
                             "feature; sharded fills take exact lengths")
        x, positions = embed_inputs(params, cfg, batch, rt)
        B, S = x.shape[:2]
        if prompt_len is None:
            self._true_S = None
        else:
            # prefix = frontend tokens (patches/meta) prepended by embed
            prefix = S - batch["tokens"].shape[1]
            self._true_S = jnp.asarray(prompt_len, jnp.int32) + prefix
        enc_out = None
        enc_len = 0
        if cfg.is_encoder_decoder:
            from repro.models.transformer import run_layers
            enc = batch["frames"].astype(rt.activ_dtype)
            enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None],
                                       enc.shape[:2])
            enc_out, _ = run_layers(params, cfg, enc, rt, enc_pos,
                                    stack="encoder")
            enc_out = rms_norm(enc_out, params["encoder_norm"], cfg.norm_eps)
            enc_len = enc_out.shape[1]

        cache = self.init_cache(B, max(max_context, S + 1), enc_len=enc_len)
        shared = self.eng.shared_pool
        if shared and self.mesh is not None and self.mesh.size > 1:
            raise NotImplementedError(
                "sharded one-shot prefill into a shared pool is not wired; "
                "shared-pool serving prefills via prefill_chunk (the mesh "
                "path covers decode and chunk attention)")
        if shared and self.eng.hot_pages:
            raise ValueError(
                "one-shot prefill cannot run against a TIERED pool: the "
                "identity-striped init tables would alias slots inside the "
                "hot tier's few device pages; tiered pools are managed by "
                "the serving scheduler's residency machinery (DESIGN.md "
                "§13) — run hot_pages=0 here, or serve via KVNANDServer")
        # prefill writes through the (identity-striped) tables; they are
        # read-only during the layer scan so they ride as closure constants
        self._prefill_tables = {"g": cache.page_table_g,
                                "w": cache.page_table_w}
        self._prefill_plan = plan_sharding(
            self.mesh, B, paged_kv.pool_page_count(cache.k_pages_g, shared))
        pools = self._collect(cache, POOL_G + POOL_W)
        states = self._collect(cache, STATE_LEAVES)
        cross = self._collect(cache, ("cross_k", "cross_v"))

        def layer(carry, pl_, l_idx, g_idx, w_idx, is_glob, _):
            xc, pools, states, cross_c = carry
            return self._prefill_block(
                pl_, xc, positions, enc_out, is_glob, pools, states,
                cross_c, l_idx, g_idx, w_idx), None

        (x, pools, states, cross), _ = self._walk(
            params, layer, (x, pools, states, cross))

        updates: Dict[str, Any] = dict(pools)
        updates.update(states)
        updates.update(cross)
        if self._true_S is None:
            updates["lengths"] = jnp.full((B,), S, jnp.int32)
            x_last = x[:, -1:]
        else:
            updates["lengths"] = jnp.broadcast_to(self._true_S, (B,)
                                                  ).astype(jnp.int32)
            x_last = jax.lax.dynamic_slice_in_dim(x, self._true_S - 1, 1, 1)
        if cache.page_pos_w is not None:
            NPw = cache.page_pos_w.shape[1]
            if self._true_S is None:
                updates["page_pos_w"] = self._prefill_window_pos(S, NPw, B)
            else:
                vals = paged_kv.window_page_positions_dyn(
                    self._true_S, NPw, self.eng.page_tokens)
                updates["page_pos_w"] = jnp.broadcast_to(vals[None],
                                                         (B, NPw))
        cache = dataclasses.replace(cache, **updates)
        logits = lm_head_logits(params, cfg, x_last)[:, 0]
        return logits, cache

    def _prefill_window_pos(self, S: int, NPw: int, B: int):
        vals = paged_kv.window_page_positions(S, NPw, self.eng.page_tokens)
        return jnp.broadcast_to(jnp.asarray(vals)[None], (B, NPw))

    def _prefill_block(self, pl_, x, positions, enc_out, is_glob, pools,
                       states, cross, l_idx, g_idx, w_idx):
        cfg, rt = self.cfg, self.rt
        B, S = x.shape[:2]

        if cfg.family == "ssm":
            x, states = self._rwkv_prefill_block(pl_, x, states, l_idx)
            return x, pools, states, cross

        h = rms_norm(x, pl_["ln1"], cfg.norm_eps)
        q, k, v = attn_mod.project_qkv(pl_["attn"], cfg, h, positions,
                                       rope=attn_mod.use_rope(cfg, is_glob))
        window = cfg.window if (cfg.window and not is_glob) else None
        o = attn_mod.sharded_flash_attention(
            q, k, v, causal=True, window=window, impl=rt.attn_impl)
        aout = attn_mod.project_out(pl_["attn"], cfg, o, h)

        use_window = (cfg.window is not None) and not is_glob
        plan = self._prefill_plan
        sharded = self.mesh is not None and self.mesh.size > 1
        fmt = self.eng.kv_quant

        # ONE fill path for every arch/format/layout: the one-shot fill is
        # `prefill_chunk`'s whole-prompt chunk write (`paged_kv.fill_layer`
        # — bit-identical pages, see the chunk parity tests); only the
        # mesh-sharded stripe fills keep their shard-local writers.
        # Global-pool bucket padding needs no valid-length guard — padded
        # pages land after the true length and stay masked by `lengths`.
        suffix = "w" if use_window else "g"
        idx = w_idx if use_window else g_idx
        page_axes = plan.page_axes_w if use_window else plan.page_axes_g
        for prefix, kv_seq in (("k", k), ("v", v)):
            name = f"{prefix}_pages_{suffix}"
            sname = f"{prefix}_scale_{suffix}"
            if sharded and page_axes:
                sfill = (seqpar.sharded_window_fill if use_window
                         else seqpar.sharded_prefill_fill)
                out = sfill(pools[name], kv_seq, idx, mesh=self.mesh,
                            batch_axes=plan.batch_axes, page_axes=page_axes,
                            scale=pools.get(sname), kv_quant=fmt)
            else:
                out = paged_kv.fill_layer(
                    pools[name], kv_seq, idx, ring=use_window,
                    true_len=self._true_S if use_window else None,
                    table=self._prefill_tables[suffix]
                    if self.eng.shared_pool else None,
                    scale=pools.get(sname), kv_quant=fmt)
            if fmt != "none":
                pools[name], pools[sname] = out
            else:
                pools[name] = out

        if cfg.family == "hybrid":
            state0 = jnp.zeros(states["ssm_state"].shape[1:], jnp.float32)
            tail0 = jnp.zeros(states["conv_tail"].shape[1:],
                              states["conv_tail"].dtype)
            sout, s_new, tail_new = ssm_mod.ssm_mixer(
                pl_["ssm"], cfg, h, state0, tail0)
            aout = (aout + sout) * 0.5
            states["ssm_state"] = states["ssm_state"].at[l_idx].set(s_new)
            states["conv_tail"] = states["conv_tail"].at[l_idx].set(
                tail_new.astype(states["conv_tail"].dtype))
        x = x + post_norm(pl_, "ln1_post", cfg, aout)

        if cfg.is_encoder_decoder and enc_out is not None:
            h = rms_norm(x, pl_["ln_cross"], cfg.norm_eps)
            x = x + attn_mod.attention_train(pl_["cross"], cfg, h,
                                             kv_x=enc_out, impl=rt.attn_impl)
            kv_dt = jnp.dtype(self.eng.kv_dtype)
            ck = attn_mod._proj(pl_["cross"], "wk", enc_out).astype(kv_dt)
            cv = attn_mod._proj(pl_["cross"], "wv", enc_out).astype(kv_dt)
            cross["cross_k"] = cross["cross_k"].at[l_idx].set(ck)
            cross["cross_v"] = cross["cross_v"].at[l_idx].set(cv)

        x, _ = self._ffn_half(pl_, x)
        return x, pools, states, cross

    def _rwkv_prefill_block(self, pl_, x, states, l_idx):
        cfg = self.cfg
        B = x.shape[0]
        h = rms_norm(x, pl_["ln1"], cfg.norm_eps)
        state0 = jnp.zeros(states["rwkv_state"].shape[1:], jnp.float32)
        shift0 = jnp.zeros((B, cfg.d_model), h.dtype)
        tout, s_new, shift_new = rwkv_mod.rwkv_timemix(
            pl_["tmix"], cfg, h, state0, shift0)
        x = x + tout
        h = rms_norm(x, pl_["ln2"], cfg.norm_eps)
        cm = pl_["cmix"]
        h_prev = jnp.concatenate([jnp.zeros_like(h[:, :1]), h[:, :-1]],
                                 axis=1)
        xk = h + (h_prev - h) * cm["mu_k"].astype(h.dtype)
        xr = h + (h_prev - h) * cm["mu_r"].astype(h.dtype)
        kk = jnp.square(jax.nn.relu(dense(cm, "ck", xk)))
        vv = dense(cm, "cv", kk)
        rr = jax.nn.sigmoid(dense(cm, "cr", xr))
        x = x + rr * vv
        states["rwkv_state"] = states["rwkv_state"].at[l_idx].set(s_new)
        states["rwkv_shift"] = states["rwkv_shift"].at[l_idx].set(
            shift_new.astype(states["rwkv_shift"].dtype))
        states["rwkv_shift2"] = states["rwkv_shift2"].at[l_idx].set(
            h[:, -1].astype(states["rwkv_shift2"].dtype))
        return x, states

    # ------------------------------------------------------------------
    # chunked prefill (interleaved continuous batching)
    # ------------------------------------------------------------------
    def prefill_chunk(self, params, cache: DecodeCache,
                      batch: Dict[str, jax.Array], slot, start, chunk_len,
                      *, first: bool = False):
        """Process one page-aligned chunk of ONE slot's prompt directly
        into that slot's stripe of the SHARED paged pool.

        This replaces the admit-time "prefill into a one-sequence cache,
        then splice" dance: each chunk's K/V lands exactly once, in place,
        so admission costs O(chunk) instead of O(prompt) + O(pool-splice),
        and a chunk can share a scheduler step with the decode batch.

        batch["tokens"]: [1, C] chunk tokens (C static — the scheduler's
        chunk bucket); slot/start/chunk_len: traced scalars — the batch
        row, the absolute cache position of the chunk's first token
        (page-aligned: ``start % page_tokens == 0``), and the number of
        valid tokens in the chunk (the rest is bucket padding).
        first=True (static) routes through `embed_inputs` so frontend
        prefixes (hymba meta tokens) are prepended, and skips the
        past-context partial; it is required for ssm/hybrid continuations
        to start from zero state, and for any arch whose prefix would
        break page alignment of later chunks (those use one whole-prompt
        chunk).

        Per attention layer the chunk runs two partial attentions merged
        by log-sum-exp (the NPU softmax-aggregation of Fig 8, applied at
        chunk granularity): a causal in-chunk partial over the chunk's own
        fresh K/V, and a past-context partial read from the slot's already
        written pages (dequantized page-wise for kv8/kv4 pools) — then the
        chunk's K/V are filled into the stripe as whole pages (quantized
        pools get bit-identical codes to the one-shot prefill fill).
        Recurrent families carry (state, shift) per slot instead.

        Returns (logits [1, V] at the chunk's last valid token, cache).
        The scheduler samples from the logits only on the final chunk.
        """
        cfg, rt = self.cfg, self.rt
        if cfg.is_encoder_decoder:
            raise ValueError("chunked prefill does not support "
                             "encoder-decoder archs (cross-KV is built by "
                             "full prefill)")
        mesh_on = self.mesh is not None and self.mesh.size > 1
        if mesh_on and (cfg.window is not None
                        or cfg.family in ("ssm", "hybrid")):
            raise NotImplementedError(
                "sharded chunked prefill covers global-pool attention "
                "archs; window-ring / recurrent archs are single-host")
        shared = self.eng.shared_pool
        if mesh_on and shared:
            raise NotImplementedError(
                "sharded chunked prefill into a shared pool is not wired "
                "(the mesh path covers shared-pool decode); run the "
                "scheduler single-host or use the stripe layout on a mesh")
        slot = jnp.asarray(slot, jnp.int32)
        start = jnp.asarray(start, jnp.int32)
        chunk_len = jnp.asarray(chunk_len, jnp.int32)

        if first:
            x, _ = embed_inputs(params, cfg, batch, rt)
        else:
            x = embed_tokens(params, cfg, batch["tokens"], rt)
        B1, S = x.shape[:2]
        prefix = S - batch["tokens"].shape[1]
        q_pos = start + jnp.arange(S, dtype=jnp.int32)
        positions = q_pos[None]
        v_len = chunk_len + prefix                 # valid extent incl prefix
        end = start + v_len
        T = self.eng.page_tokens
        page0 = start // T

        B = cache.lengths.shape[0]
        plan = plan_sharding(
            self.mesh, B, paged_kv.pool_page_count(cache.k_pages_g, shared))
        zero = jnp.zeros((), jnp.int32)

        # per-call temporaries shared by every layer of the scan
        self._ck = dict(slot=slot, start=start, page0=page0, v_len=v_len,
                        q_pos=q_pos, first=first, plan=plan, mesh_on=mesh_on,
                        shared=shared)
        if cache.page_table_g is not None:
            NPg = cache.page_table_g.shape[1]
            trow = jax.lax.dynamic_slice(cache.page_table_g, (slot, zero),
                                         (1, NPg))
            if shared:
                # attention/fills walk LOGICAL pages through the row, so
                # logical page j's base is j·T; stale/unallocated entries
                # are masked by `pos < start` in the past partial
                self._ck["trow_g"] = trow[0]
                self._ck["base_g"] = jnp.broadcast_to(
                    (jnp.arange(NPg, dtype=jnp.int32) * T)[None], (1, NPg))
            else:
                self._ck["base_g"] = jnp.zeros((1, NPg), jnp.int32).at[
                    0, trow[0]].set(jnp.arange(NPg, dtype=jnp.int32) * T)
        if cache.page_pos_w is not None:
            NPw = cache.page_pos_w.shape[1]
            # ring state BEFORE this chunk; chunk 0 rewrote the row, so a
            # recycled occupant's stale bases are already gone
            self._ck["pos_w"] = jax.lax.dynamic_slice(
                cache.page_pos_w, (slot, zero), (1, NPw))
            if shared:
                self._ck["trow_w"] = jax.lax.dynamic_slice(
                    cache.page_table_w, (slot, zero), (1, NPw))[0]

        pools = self._collect(cache, POOL_G + POOL_W)
        states = self._collect(cache, STATE_LEAVES)

        def layer(carry, pl_, l_idx, g_idx, w_idx, is_glob, _):
            xc, pools, states = carry
            return self._chunk_block(pl_, xc, positions, is_glob, pools,
                                     states, l_idx, g_idx, w_idx), None

        (x, pools, states), _ = self._walk(params, layer, (x, pools, states))

        updates: Dict[str, Any] = dict(pools)
        updates.update(states)
        updates["lengths"] = jax.lax.dynamic_update_slice(
            cache.lengths, jnp.reshape(end, (1,)).astype(cache.lengths.dtype),
            (slot,))
        if cache.page_pos_w is not None:
            NPw = cache.page_pos_w.shape[1]
            vals = paged_kv.window_page_positions_dyn(end, NPw, T)
            updates["page_pos_w"] = jax.lax.dynamic_update_slice(
                cache.page_pos_w, vals[None], (slot, zero))
        cache = dataclasses.replace(cache, **updates)
        x_last = jax.lax.dynamic_slice_in_dim(x, v_len - 1, 1, 1)
        with jax.named_scope("logits"):
            logits = lm_head_logits(params, cfg, x_last)[:, 0]
        return logits, cache

    def _chunk_past_partial(self, pools, kname, vname, ksname, vsname, idx,
                            q, base, window, trow=None):
        """Past-context partial of the chunk queries vs the slot's pages.

        Stripe layout slices the slot's private stripe; shared pools pass
        the layer's GLOBAL pool plus the slot's table row (`trow`)."""
        ck = self._ck
        fmt = self.eng.kv_quant
        from repro.kernels.paged_attention import paged_chunk_attention
        if ck["shared"]:
            with jax.named_scope("pool_view"):
                kp = self._layer_slice(pools[kname], idx)  # [K, P, Ts, dh]
                vp = self._layer_slice(pools[vname], idx)
                ks = vs = None
                if fmt != "none":
                    ks = self._layer_slice(pools[ksname], idx)
                    vs = self._layer_slice(pools[vsname], idx)
            with jax.named_scope("paged_attn"):
                return paged_chunk_attention(
                    q, kp, vp, base, ck["start"], ck["q_pos"],
                    window=window, impl=self.eng.attn_impl, kv_quant=fmt,
                    k_scale=ks, v_scale=vs, page_table=trow[None],
                    partitions=self.eng.attn_partitions)
        Lp, B, K, NP, Ts, dh = pools[kname].shape
        zero = jnp.zeros((), jnp.int32)
        pidx = (idx, ck["slot"], zero, zero, zero, zero)
        with jax.named_scope("pool_view"):
            kp = jax.lax.dynamic_slice(pools[kname], pidx,
                                       (1, 1, K, NP, Ts, dh))[0]
            vp = jax.lax.dynamic_slice(pools[vname], pidx,
                                       (1, 1, K, NP, Ts, dh))[0]
            ks = vs = None
            if fmt != "none":
                sidx = pidx[:4]
                ks = jax.lax.dynamic_slice(pools[ksname], sidx,
                                           (1, 1, K, NP))[0]
                vs = jax.lax.dynamic_slice(pools[vsname], sidx,
                                           (1, 1, K, NP))[0]
        with jax.named_scope("paged_attn"):
            if ck["mesh_on"] and ck["plan"].page_axes_g:
                return seqpar.sharded_chunk_attention(
                    q, kp, vp, base, ck["start"], ck["q_pos"], self.mesh,
                    window=window, page_axes=ck["plan"].page_axes_g,
                    impl=self.eng.attn_impl, kv_quant=fmt,
                    k_scale=ks, v_scale=vs,
                    partitions=self.eng.attn_partitions)
            return paged_chunk_attention(
                q, kp, vp, base, ck["start"], ck["q_pos"], window=window,
                impl=self.eng.attn_impl, kv_quant=fmt, k_scale=ks,
                v_scale=vs, partitions=self.eng.attn_partitions)

    def _chunk_block(self, pl_, x, positions, is_glob, pools, states,
                     l_idx, g_idx, w_idx):
        cfg, rt = self.cfg, self.rt
        ck = self._ck

        if cfg.family == "ssm":
            return self._rwkv_chunk_block(pl_, x, pools, states, l_idx)

        h = rms_norm(x, pl_["ln1"], cfg.norm_eps)
        with jax.named_scope("qkv"):
            q, k, v = attn_mod.project_qkv(
                pl_["attn"], cfg, h, positions,
                rope=attn_mod.use_rope(cfg, is_glob))
        use_window = (cfg.window is not None) and not is_glob
        window = cfg.window if use_window else None
        scale = cfg.d_head ** -0.5

        # in-chunk causal partial over the chunk's own (full-precision) K/V
        with jax.named_scope("paged_attn"):
            o, m, l = seqpar._attn_block_partial(
                q, k, v, ck["q_pos"], ck["start"], causal=True,
                window=window, is_global=None, scale=scale)
        if not ck["first"]:
            # past-context partial from the already-written pages
            if use_window:
                o2, m2, l2 = self._chunk_past_partial(
                    pools, "k_pages_w", "v_pages_w", "k_scale_w",
                    "v_scale_w", w_idx, q, ck["pos_w"], window,
                    trow=ck.get("trow_w"))
            else:
                o2, m2, l2 = self._chunk_past_partial(
                    pools, "k_pages_g", "v_pages_g", "k_scale_g",
                    "v_scale_g", g_idx, q, ck["base_g"], None,
                    trow=ck.get("trow_g"))
            with jax.named_scope("paged_attn"):
                o, m, l = seqpar.merge_two(o, m, l, o2, m2, l2)
        with jax.named_scope("attn_out"):
            aout = attn_mod.project_out(pl_["attn"], cfg,
                                        o.astype(h.dtype), h)

        # fill the chunk's K/V into the slot's pages (whole pages, in place)
        fmt = self.eng.kv_quant
        if use_window:
            names = ("k_pages_w", "v_pages_w", "k_scale_w", "v_scale_w")
            fill_idx, fill = w_idx, paged_kv.fill_chunk_window_at
            fill_sh, trow = paged_kv.fill_chunk_window_at_shared, \
                ck.get("trow_w")
        else:
            names = ("k_pages_g", "v_pages_g", "k_scale_g", "v_scale_g")
            fill_idx, fill = g_idx, paged_kv.fill_chunk_global_at
            fill_sh, trow = paged_kv.fill_chunk_global_at_shared, \
                ck.get("trow_g")
        for prefix_, kv_seq in (("k", k), ("v", v)):
            name = names[0] if prefix_ == "k" else names[1]
            sname = names[2] if prefix_ == "k" else names[3]
            with jax.named_scope("kv_append"):
                if (ck["mesh_on"] and ck["plan"].page_axes_g
                        and not use_window):
                    out = seqpar.sharded_chunk_fill(
                        pools[name], kv_seq, fill_idx, ck["slot"],
                        ck["page0"], ck["v_len"], self.mesh,
                        batch_axes=ck["plan"].batch_axes,
                        page_axes=ck["plan"].page_axes_g,
                        scale=pools.get(sname), kv_quant=fmt)
                elif ck["shared"]:
                    out = fill_sh(pools[name], kv_seq, fill_idx, trow,
                                  ck["page0"], ck["v_len"],
                                  scale=pools.get(sname), kv_quant=fmt)
                else:
                    out = fill(pools[name], kv_seq, fill_idx, ck["slot"],
                               ck["page0"], ck["v_len"],
                               scale=pools.get(sname), kv_quant=fmt)
            if fmt != "none":
                pools[name], pools[sname] = out
            else:
                pools[name] = out

        if cfg.family == "hybrid":
            Hs = states["ssm_state"].shape
            Ts_ = states["conv_tail"].shape
            if ck["first"]:
                s0 = jnp.zeros((1,) + Hs[2:], jnp.float32)
                t0 = jnp.zeros((1,) + Ts_[2:], states["conv_tail"].dtype)
            else:
                s0 = jax.lax.dynamic_slice(
                    states["ssm_state"], (l_idx, ck["slot"], 0, 0),
                    (1, 1) + Hs[2:])[0]
                t0 = jax.lax.dynamic_slice(
                    states["conv_tail"], (l_idx, ck["slot"], 0, 0),
                    (1, 1) + Ts_[2:])[0]
            sout, s_new, tail_new = ssm_mod.ssm_mixer(
                pl_["ssm"], cfg, h, s0, t0)
            aout = (aout + sout) * 0.5
            states["ssm_state"] = jax.lax.dynamic_update_slice(
                states["ssm_state"], s_new[None].astype(jnp.float32),
                (l_idx, ck["slot"], 0, 0))
            states["conv_tail"] = jax.lax.dynamic_update_slice(
                states["conv_tail"],
                tail_new[None].astype(states["conv_tail"].dtype),
                (l_idx, ck["slot"], 0, 0))
        x = x + post_norm(pl_, "ln1_post", cfg, aout)
        x, _ = self._ffn_half(pl_, x)
        return x, pools, states

    def _rwkv_chunk_block(self, pl_, x, pools, states, l_idx):
        cfg = self.cfg
        ck = self._ck
        h = rms_norm(x, pl_["ln1"], cfg.norm_eps)
        Hs = states["rwkv_state"].shape
        if ck["first"]:
            st0 = jnp.zeros((1,) + Hs[2:], jnp.float32)
            sh0 = jnp.zeros((1, cfg.d_model), h.dtype)
            sh2 = jnp.zeros((1, cfg.d_model), h.dtype)
        else:
            st0 = jax.lax.dynamic_slice(
                states["rwkv_state"], (l_idx, ck["slot"], 0, 0, 0),
                (1, 1) + Hs[2:])[0]
            sh0 = jax.lax.dynamic_slice(
                states["rwkv_shift"], (l_idx, ck["slot"], 0),
                (1, 1, cfg.d_model))[0].astype(h.dtype)
            sh2 = jax.lax.dynamic_slice(
                states["rwkv_shift2"], (l_idx, ck["slot"], 0),
                (1, 1, cfg.d_model))[0].astype(h.dtype)
        tout, s_new, shift_new = rwkv_mod.rwkv_timemix(
            pl_["tmix"], cfg, h, st0, sh0)
        x = x + tout
        h = rms_norm(x, pl_["ln2"], cfg.norm_eps)
        cm = pl_["cmix"]
        h_prev = jnp.concatenate([sh2[:, None], h[:, :-1]], axis=1)
        xk = h + (h_prev - h) * cm["mu_k"].astype(h.dtype)
        xr = h + (h_prev - h) * cm["mu_r"].astype(h.dtype)
        kk = jnp.square(jax.nn.relu(dense(cm, "ck", xk)))
        vv = dense(cm, "cv", kk)
        rr = jax.nn.sigmoid(dense(cm, "cr", xr))
        x = x + rr * vv
        states["rwkv_state"] = jax.lax.dynamic_update_slice(
            states["rwkv_state"], s_new[None].astype(jnp.float32),
            (l_idx, ck["slot"], 0, 0, 0))
        states["rwkv_shift"] = jax.lax.dynamic_update_slice(
            states["rwkv_shift"],
            shift_new[None].astype(states["rwkv_shift"].dtype),
            (l_idx, ck["slot"], 0))
        states["rwkv_shift2"] = jax.lax.dynamic_update_slice(
            states["rwkv_shift2"],
            h[:, -1][None].astype(states["rwkv_shift2"].dtype),
            (l_idx, ck["slot"], 0))
        return x, pools, states
