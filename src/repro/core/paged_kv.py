"""Page-level KV cache (paper §IV-D) adapted to TPU sharding.

Layout is (layer, head)-major exactly as Fig 11(b): pages never mix layers or
heads, so the paged-attention kernel streams whole pages HBM→VMEM with full
spatial locality — the TPU analogue of eliminating flash page-read
amplification.

Two physical layouts share every read/write path:

  stripe (default)            shared pool (EngineConfig.shared_pool)
  k/v_pages: [L, B, K, NP, T, dh]   k/v_pages: [L, K, P_total, T, dh]
      L  stacked layers (scanned)        B  sequences (sharded over `data`)
      K  kv heads                        NP logical pages per sequence
      T  page_tokens                     P_total pool pages (sharded over
                                           `model` — the paper's G2 dies)

In the stripe layout each slot owns a private run of NP physical pages
sized to max_context; `page_table` permutes only within the stripe.  In
the SHARED layout (the paper's §IV-D FTL mapping proper) all slots draw
pages from one pool per layer-group: `page_table_g/_w: [B, NP] -> phys`
hold global physical indices handed out by the host-side free-page
allocator (`core/page_alloc.py`), so a 128-token request holds 2 pages
while a 100K-token one holds thousands — admission is bounded by actual
KV footprint, prefixes can be shared copy-on-write, and unallocated
logical pages stay data-invalid (their token positions lie beyond
`lengths`).

Two page pools per model when the arch mixes attention spans:
  * global pool — NP covers the full context;
  * window pool — NP covers only the sliding window, recycled as a ring
    (the paper's "access-aware block allocation": stale pages are retired
    and their slots reused, bounding both capacity and — in flash terms —
    read-disturb accumulation).

`page_pos` records each physical page's base token position so window
validity is derived from data, not control flow.

The writer family, layout by layout: one-shot/chunk fills
(`fill_layer`, `fill_chunk_*`), single-token appends (`append_*`,
`append_token_quant*`), and the accept-gated multi-token span appends
(`append_span*`) that speculative verification uses — every write path
shares the same drop-sentinel convention, so an out-of-range physical
index discards the write instead of corrupting a live page.

Recurrent families store O(1) state instead (rwkv/ssm fields); hybrids carry
both; encoder-decoder carries precomputed cross-attention K/V.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EngineConfig, ModelConfig
from repro.core import quant
from repro.models import ssm as ssm_mod


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def pool_page_count(pool_leaf, shared: bool) -> int:
    """Physical pages of a k/v pool leaf: the page axis sits at index 2
    in the shared layout [L, K, P, T, dh], index 3 in the stripe layout
    [L, B, K, NP, T, dh]; 1 when the arch has no such pool."""
    if pool_leaf is None:
        return 1
    return pool_leaf.shape[2 if shared else 3]


# ---------------------------------------------------------------------------
# Layer grouping: smallest repeating local/global period (scan-friendly)
# ---------------------------------------------------------------------------

def layer_pattern(cfg: ModelConfig, first: int = 0, n: Optional[int] = None
                  ) -> Tuple[int, Tuple[bool, ...]]:
    """Returns (period, pattern) of layers [first, first + n) (default:
    every layer) with pattern[i] == layer first + i is global."""
    n = cfg.n_layers - first if n is None else n
    flags = tuple(cfg.is_global_layer(i) for i in range(first, first + n))
    for p in range(1, n + 1):
        if n % p:
            continue
        if all(flags[i] == flags[i % p] for i in range(n)):
            return p, flags[:p]
    return n, flags


# ---------------------------------------------------------------------------
# Cache container
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclass
class DecodeCache:
    """Pytree of per-request decode state (all leaves optional)."""
    # paged attention KV — global-span layers
    k_pages_g: Optional[jax.Array] = None   # [Lg, B, K, NPg, T, dh]
    v_pages_g: Optional[jax.Array] = None   # (shared: [Lg, K, Pg, T, dh])
    page_table_g: Optional[jax.Array] = None  # [B, NPg] logical -> physical
    # paged attention KV — sliding-window layers (ring-recycled)
    k_pages_w: Optional[jax.Array] = None   # [Lw, B, K, NPw, T, dh]
    v_pages_w: Optional[jax.Array] = None   # (shared: [Lw, K, Pw, T, dh])
    page_table_w: Optional[jax.Array] = None  # [B, NPw] ring slot -> physical
    page_pos_w: Optional[jax.Array] = None  # [B, NPw] base token position
    # per-page × per-kv-head dequant scales (kv8/kv4 pools only)
    # (shared: [Lg, K, Pg] — one scale vector per physical pool page)
    k_scale_g: Optional[jax.Array] = None   # [Lg, B, K, NPg] f32
    v_scale_g: Optional[jax.Array] = None
    k_scale_w: Optional[jax.Array] = None   # [Lw, B, K, NPw] f32
    v_scale_w: Optional[jax.Array] = None
    # recurrent state
    rwkv_state: Optional[jax.Array] = None  # [L, B, H, dh, dh]
    rwkv_shift: Optional[jax.Array] = None  # [L, B, D] time-mix token shift
    rwkv_shift2: Optional[jax.Array] = None  # [L, B, D] channel-mix shift
    ssm_state: Optional[jax.Array] = None   # [L, B, D, N]
    conv_tail: Optional[jax.Array] = None   # [L, B, CONV_K-1, D]
    # encoder-decoder cross attention (read-only after prefill)
    cross_k: Optional[jax.Array] = None     # [L, B, Senc, K, dh]
    cross_v: Optional[jax.Array] = None
    # bookkeeping
    lengths: Optional[jax.Array] = None     # [B] tokens written so far


# window rings hold a whole number of these page blocks: the Pallas decode
# kernel walks pages in blocks of up to 8 (`paged_attention_partial`'s
# `pages_per_block`), which must divide the ring
RING_PAGE_BLOCK = 8


def _n_layers_split(cfg: ModelConfig) -> Tuple[int, int]:
    n_global = sum(cfg.is_global_layer(i) for i in range(cfg.n_layers))
    return n_global, cfg.n_layers - n_global


def cache_spec(cfg: ModelConfig, eng: EngineConfig, batch: int,
               max_context: int, *, dtype=jnp.bfloat16,
               enc_len: int = 0, page_shards_g: int = 1,
               page_shards_w: int = 1) -> Dict[str, Any]:
    """Abstract shapes for every cache leaf of this (arch, context).

    page_shards_*: round each pool's page count up to a multiple of the
    number of mesh shards holding the page axis.
    """
    T = eng.page_tokens
    K, dh, D = cfg.n_kv_heads, cfg.d_head, cfg.d_model
    Lg, Lw = _n_layers_split(cfg)
    spec: Dict[str, Any] = {}

    def round_np(np_raw: int, shards: int) -> int:
        return max(ceil_div(np_raw, shards), 1) * shards

    # quantized pools store packed int codes + per-page×head f32 scales
    fmt = eng.kv_quant
    if fmt != "none":
        Ts = quant.kv_page_tokens_stored(T, fmt)
        pool_dt = quant.kv_storage_dtype(fmt)
    else:
        Ts, pool_dt = T, dtype

    has_attn = cfg.family != "ssm"
    if has_attn:
        if Lg:
            NPg = eng.max_pages_per_seq or ceil_div(max_context, T)
            NPg = round_np(NPg, page_shards_g)
            if eng.shared_pool:
                # tiered hierarchy (DESIGN.md §13): only the HOT tier is
                # device-resident — the flash-total page count lives in
                # the allocator, not in this pool.
                Pg_flash = eng.total_pages or batch * NPg
                Pg = round_np(eng.hot_pages or Pg_flash, page_shards_g)
                spec["k_pages_g"] = ((Lg, K, Pg, Ts, dh), pool_dt)
                spec["v_pages_g"] = ((Lg, K, Pg, Ts, dh), pool_dt)
                if fmt != "none":
                    spec["k_scale_g"] = ((Lg, K, Pg), jnp.float32)
                    spec["v_scale_g"] = ((Lg, K, Pg), jnp.float32)
            else:
                spec["k_pages_g"] = ((Lg, batch, K, NPg, Ts, dh), pool_dt)
                spec["v_pages_g"] = ((Lg, batch, K, NPg, Ts, dh), pool_dt)
                if fmt != "none":
                    spec["k_scale_g"] = ((Lg, batch, K, NPg), jnp.float32)
                    spec["v_scale_g"] = ((Lg, batch, K, NPg), jnp.float32)
            spec["page_table_g"] = ((batch, NPg), jnp.int32)
        if Lw:
            # the ring: the window's pages plus the one being filled,
            # rounded up to whole blocks of the decode kernel's page walk
            # (stale ring pages fall outside the window and are masked)
            NPw = round_np(ceil_div(ceil_div(cfg.window, T) + 1,
                                    RING_PAGE_BLOCK) * RING_PAGE_BLOCK,
                           page_shards_w)
            if eng.shared_pool:
                Pw = round_np(eng.total_pages_w or batch * NPw,
                              page_shards_w)
                spec["k_pages_w"] = ((Lw, K, Pw, Ts, dh), pool_dt)
                spec["v_pages_w"] = ((Lw, K, Pw, Ts, dh), pool_dt)
                spec["page_table_w"] = ((batch, NPw), jnp.int32)
                if fmt != "none":
                    spec["k_scale_w"] = ((Lw, K, Pw), jnp.float32)
                    spec["v_scale_w"] = ((Lw, K, Pw), jnp.float32)
            else:
                spec["k_pages_w"] = ((Lw, batch, K, NPw, Ts, dh), pool_dt)
                spec["v_pages_w"] = ((Lw, batch, K, NPw, Ts, dh), pool_dt)
                if fmt != "none":
                    spec["k_scale_w"] = ((Lw, batch, K, NPw), jnp.float32)
                    spec["v_scale_w"] = ((Lw, batch, K, NPw), jnp.float32)
            spec["page_pos_w"] = ((batch, NPw), jnp.int32)
    if cfg.family == "ssm":
        H = cfg.n_heads
        spec["rwkv_state"] = ((cfg.n_layers, batch, H, dh, dh), jnp.float32)
        spec["rwkv_shift"] = ((cfg.n_layers, batch, D), dtype)
        spec["rwkv_shift2"] = ((cfg.n_layers, batch, D), dtype)
    if cfg.family == "hybrid":
        spec["ssm_state"] = ((cfg.n_layers, batch, D, cfg.ssm_state),
                             jnp.float32)
        spec["conv_tail"] = ((cfg.n_layers, batch, ssm_mod.CONV_K - 1, D),
                             dtype)
    if cfg.is_encoder_decoder and enc_len:
        spec["cross_k"] = ((cfg.n_layers, batch, enc_len, K, dh), dtype)
        spec["cross_v"] = ((cfg.n_layers, batch, enc_len, K, dh), dtype)
    spec["lengths"] = ((batch,), jnp.int32)
    return spec


CACHE_AXES: Dict[str, Tuple] = {
    # logical axes per leaf (mapped by distributed.sharding rules)
    "k_pages_g": ("layer", "batch", None, "kv_pages", None, None),
    "v_pages_g": ("layer", "batch", None, "kv_pages", None, None),
    "page_table_g": ("batch", None),
    "k_pages_w": ("layer", "batch", None, "kv_pages", None, None),
    "v_pages_w": ("layer", "batch", None, "kv_pages", None, None),
    "page_table_w": ("batch", None),
    "page_pos_w": ("batch", None),
    "k_scale_g": ("layer", "batch", None, "kv_pages"),
    "v_scale_g": ("layer", "batch", None, "kv_pages"),
    "k_scale_w": ("layer", "batch", None, "kv_pages"),
    "v_scale_w": ("layer", "batch", None, "kv_pages"),
    "rwkv_state": ("layer", "batch", None, None, None),
    "rwkv_shift": ("layer", "batch", "embed"),
    "rwkv_shift2": ("layer", "batch", "embed"),
    "ssm_state": ("layer", "batch", None, None),
    "conv_tail": ("layer", "batch", None, "embed"),
    "cross_k": ("layer", "batch", "act_seq", None, None),
    "cross_v": ("layer", "batch", "act_seq", None, None),
    "lengths": ("batch",),
}

# shared-pool leaves drop the batch dim: the physical page axis carries the
# `kv_pages` (model) sharding instead of a per-slot stripe
SHARED_CACHE_AXES: Dict[str, Tuple] = {
    "k_pages_g": ("layer", None, "kv_pages", None, None),
    "v_pages_g": ("layer", None, "kv_pages", None, None),
    "k_pages_w": ("layer", None, "kv_pages", None, None),
    "v_pages_w": ("layer", None, "kv_pages", None, None),
    "k_scale_g": ("layer", None, "kv_pages"),
    "v_scale_g": ("layer", None, "kv_pages"),
    "k_scale_w": ("layer", None, "kv_pages"),
    "v_scale_w": ("layer", None, "kv_pages"),
}


def abstract_cache(cfg: ModelConfig, eng: EngineConfig, batch: int,
                   max_context: int, *, dtype=jnp.bfloat16,
                   enc_len: int = 0, page_shards_g: int = 1,
                   page_shards_w: int = 1) -> DecodeCache:
    spec = cache_spec(cfg, eng, batch, max_context, dtype=dtype,
                      enc_len=enc_len, page_shards_g=page_shards_g,
                      page_shards_w=page_shards_w)
    return DecodeCache(**{k: jax.ShapeDtypeStruct(s, d)
                          for k, (s, d) in spec.items()})


def init_cache(cfg: ModelConfig, eng: EngineConfig, batch: int,
               max_context: int, *, dtype=jnp.bfloat16,
               enc_len: int = 0, page_shards_g: int = 1,
               page_shards_w: int = 1) -> DecodeCache:
    spec = cache_spec(cfg, eng, batch, max_context, dtype=dtype,
                      enc_len=enc_len, page_shards_g=page_shards_g,
                      page_shards_w=page_shards_w)
    leaves = {}
    shared = eng.shared_pool
    for k, (shape, dt) in spec.items():
        if k in ("page_table_g", "page_table_w"):
            B, NP = shape
            if shared:
                # identity stripes mod pool size: slot b's logical page j
                # starts on physical page b·NP + j (the allocator-free
                # default used by one-shot prefill and parity tests; the
                # scheduler overwrites tables from its allocator)
                pool_key = "k_pages_g" if k == "page_table_g" else \
                    "k_pages_w"
                P = spec[pool_key][0][2]
                rows = (jnp.arange(B, dtype=jnp.int32)[:, None] * NP
                        + jnp.arange(NP, dtype=jnp.int32)[None]) % P
                leaves[k] = rows
            else:
                leaves[k] = jnp.broadcast_to(
                    jnp.arange(NP, dtype=jnp.int32)[None], shape)
        elif k == "page_pos_w":
            leaves[k] = jnp.full(shape, -(10 ** 9), jnp.int32)
        else:
            leaves[k] = jnp.zeros(shape, dt)
    return DecodeCache(**leaves)


def cache_logical_axes(cache: DecodeCache) -> DecodeCache:
    """Mirror of the cache with logical-axis tuples (None leaves preserved).

    Shared-pool caches (pool leaves without the batch dim) pick the
    matching-rank axes from SHARED_CACHE_AXES.
    """
    out = {}
    for f in dataclasses.fields(cache):
        leaf = getattr(cache, f.name)
        if leaf is None:
            out[f.name] = None
            continue
        axes = CACHE_AXES[f.name]
        if len(axes) != leaf.ndim:
            axes = SHARED_CACHE_AXES[f.name]
        out[f.name] = axes
    return DecodeCache(**out)


# ---------------------------------------------------------------------------
# Page write paths (token append / bulk prefill fill)
# ---------------------------------------------------------------------------

def append_global(k_pages, v_pages, page_table, lengths, k_new, v_new):
    """Append one token's K/V into the global page pool of ONE layer.

    k_pages/v_pages: [B, K, NP, T, dh]; k_new/v_new: [B, K, dh];
    lengths: [B] (current position).  Returns updated pages.
    """
    T = k_pages.shape[3]
    logical = lengths // T                                    # [B]
    slot = lengths % T
    phys = jnp.take_along_axis(page_table, logical[:, None], axis=1)[:, 0]
    b_idx = jnp.arange(k_pages.shape[0])
    k_pages = k_pages.at[b_idx, :, phys, slot].set(
        k_new.astype(k_pages.dtype), mode="drop")
    v_pages = v_pages.at[b_idx, :, phys, slot].set(
        v_new.astype(v_pages.dtype), mode="drop")
    return k_pages, v_pages


def append_window(k_pages, v_pages, page_pos, lengths, k_new, v_new):
    """Ring append for window layers; also refreshes page base positions.

    Page recycling: physical page = (t // T) mod NP (the retired page's
    slot is reused — the paper's block-reclaim analogue).
    """
    B, K, NP, T, dh = k_pages.shape
    phys = (lengths // T) % NP                                # [B]
    slot = lengths % T
    b_idx = jnp.arange(B)
    k_pages = k_pages.at[b_idx, :, phys, slot].set(
        k_new.astype(k_pages.dtype), mode="drop")
    v_pages = v_pages.at[b_idx, :, phys, slot].set(
        v_new.astype(v_pages.dtype), mode="drop")
    base = lengths - slot
    new_pos = page_pos.at[b_idx, phys].set(base, mode="drop")
    page_pos = jnp.where((slot == 0)[:, None],
                         new_pos, page_pos)
    return k_pages, v_pages, page_pos


def fill_prefill_at(pool, kv_seq, layer):
    """Bulk-write prefill K/V into ONE layer of a stacked global pool.

    pool: [L, B, K, NP, T, dh] (in-place carry); kv_seq: [B, S, K, dh];
    layer: traced index.  S tokens land in the first ceil(S/T) pages.
    (Thin wrapper over `fill_layer`, the unified one-shot/chunk writer.)
    """
    return fill_layer(pool, kv_seq, layer, ring=False)


def fill_window_at(pool, kv_seq, layer):
    """Bulk-write the newest ring pages into ONE layer of a window pool."""
    return fill_layer(pool, kv_seq, layer, ring=True)


def fill_prefill_at_quant(pool, scale, kv_seq, layer, fmt: str):
    """Quantizing variant of `fill_prefill_at` (global pool, one layer)."""
    return fill_layer(pool, kv_seq, layer, ring=False, scale=scale,
                      kv_quant=fmt)


def window_page_positions(S: int, NP: int, T: int) -> np.ndarray:
    """Static ring base positions after prefilling S tokens (-1e9 = empty)."""
    vals = np.full((NP,), -(10 ** 9), np.int64)
    n_src = ceil_div(S, T)
    for sp in range(max(0, n_src - NP), n_src):
        vals[sp % NP] = sp * T
    return vals.astype(np.int32)


def window_page_positions_dyn(true_len, NP: int, T: int) -> jax.Array:
    """`window_page_positions` for a TRACED length (bucketed prefill).

    For ring slot j the newest source page mapping there is
    ``m - ((m - j) mod NP)`` with m = n_src-1; negative -> never written.
    """
    true_len = jnp.asarray(true_len, jnp.int32)
    n_src = (true_len + T - 1) // T
    m = n_src - 1
    j = jnp.arange(NP, dtype=jnp.int32)
    sp = m - ((m - j) % NP)
    return jnp.where((sp >= 0) & (n_src > 0), sp * T,
                     -(10 ** 9)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Quantized page write paths (kv8 / kv4 pools carry per-page scales)
# ---------------------------------------------------------------------------
#
# Token appends re-quantize ONLY the touched page: read the [T, dh] page,
# dequantize with its current scale, insert the new token, recompute the
# scale, write the packed page + scale back.  Everything else in the pool
# is untouched — the append stays O(page), not O(pool).
#
# Tokens land in page order, so slots > slot of the touched page are never
# live — they hold a recycled occupant's stale K/V or bucket padding.
# Those slots are masked at read time, but they MUST NOT enter the new
# amax: a 10×-larger stale value would inflate the scale and crush the
# real tokens' precision.  The appends therefore zero the dead tail
# before requantizing.

def _zero_dead_slots(page, slot):
    """page: [..., T, dh]; keep slots 0..slot, zero the rest."""
    T = page.shape[-2]
    live = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0) <= \
        jnp.reshape(slot, (1, 1))
    return jnp.where(live, page, 0.0)


def append_token_quant_uniform(pool, scale, layer, phys, slot, val,
                               fmt: str):
    """Lockstep append into a quantized stacked pool.

    pool: [L, B, K, NP, Ts, dh] int codes; scale: [L, B, K, NP] f32;
    phys/slot: [B] uniform positions; val: [B, K, dh].
    """
    L, B, K, NP, Ts, dh = pool.shape
    zero = jnp.zeros((), jnp.int32)
    pidx = (layer, zero, zero, phys[0], zero, zero)
    qpage = jax.lax.dynamic_slice(pool, pidx,
                                  (1, B, K, 1, Ts, dh))[0, :, :, 0]
    s = jax.lax.dynamic_slice(scale, (layer, zero, zero, phys[0]),
                              (1, B, K, 1))[0, :, :, 0]        # [B, K]
    page = quant.dequantize_kv_page(qpage, s, fmt)             # [B, K, T, dh]
    page = jax.lax.dynamic_update_slice(
        page, val[:, :, None, :].astype(page.dtype),
        (zero, zero, slot[0], zero))
    page = _zero_dead_slots(page, slot[0])
    q2, s2 = quant.quantize_kv_page(page, fmt)
    pool = jax.lax.dynamic_update_slice(pool, q2[:, :, None][None], pidx)
    scale = jax.lax.dynamic_update_slice(scale, s2[:, :, None][None],
                                         (layer, zero, zero, phys[0]))
    return pool, scale


def append_token_quant(pool, scale, layer, phys, slot, val, fmt: str):
    """Ragged (per-sequence position) append into a quantized pool.

    Gathers each sequence's touched page, requantizes it with the new
    token, scatters page + scale back (continuous-batching path).
    """
    L, B, K, NP, Ts, dh = pool.shape
    b_idx = jnp.arange(B)
    qpage = pool[layer, b_idx, :, phys]                        # [B, K, Ts, dh]
    s = scale[layer, b_idx, :, phys]                           # [B, K]
    page = quant.dequantize_kv_page(qpage, s, fmt)
    page = page.at[b_idx, :, slot].set(val.astype(page.dtype))
    T = page.shape[-2]
    live = jnp.arange(T)[None, :] <= slot[:, None]             # [B, T]
    page = jnp.where(live[:, None, :, None], page, 0.0)
    q2, s2 = quant.quantize_kv_page(page, fmt)
    pool = pool.at[layer, b_idx, :, phys].set(q2, mode="drop")
    scale = scale.at[layer, b_idx, :, phys].set(s2, mode="drop")
    return pool, scale


def _paged_from_seq(kv_seq, T: int):
    """[B, S, K, dh] -> page-major [B, K, n_pages, T, dh] (zero-padded)."""
    B, S, K, dh = kv_seq.shape
    n_pages = ceil_div(S, T)
    pad = n_pages * T - S
    x = jnp.pad(kv_seq, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.reshape(B, n_pages, T, K, dh).transpose(0, 3, 1, 2, 4)


# ---------------------------------------------------------------------------
# Unified one-shot fill: the whole-prompt chunk fill (satellite: the old
# per-arch fill_prefill_at/fill_window_at(+quant, +dyn) bodies collapsed
# onto the chunk-fill writer — bit-identical pages, one code path)
# ---------------------------------------------------------------------------

def fill_layer(pool, kv_seq, layer, *, ring: bool, true_len=None,
               table=None, scale=None, kv_quant: str = "none"):
    """One-shot prefill fill of ONE layer for every batch row.

    Semantically this IS `prefill_chunk`'s fill applied to one whole-prompt
    chunk at page0 = 0 (the chunk-fill parity tests pin the page contents
    bit-identical), generalized over:

      ring      False -> global pool (logical page sp), True -> window ring
                (ring slot sp % NP, ascending so each slot keeps its
                NEWEST valid occupant);
      true_len  traced count of real tokens when kv_seq carries bucket
                padding (padding pages are never written); None -> all S
                tokens are real;
      table     shared-pool page table [B, NP] (physical ids); None ->
                stripe layout;
      kv_quant  kv8/kv4 pools quantize whole pages and return
                (pool, scale).

    The exact-length stripe global fill keeps the original fused
    single-slice write (identity mapping, every page valid — bit-identical
    to the page walk, and O(1) ops for a 500-page prompt).
    """
    Ts = pool.shape[-2]
    T = Ts * (2 if kv_quant == "kv4" else 1)
    B, S = kv_seq.shape[:2]
    if table is not None:
        return _fill_layer_shared(pool, kv_seq, layer, table, ring=ring,
                                  true_len=true_len, scale=scale,
                                  kv_quant=kv_quant)
    NP = pool.shape[3]
    if not ring and true_len is None:
        x = _paged_from_seq(kv_seq, T)             # [B, K, n_pages, Ts, dh]
        zero = jnp.zeros((), jnp.int32)
        if kv_quant != "none":
            q, s = quant.quantize_kv_page(x, kv_quant)
            pool = jax.lax.dynamic_update_slice(
                pool, q[None], (layer, zero, zero, zero, zero, zero))
            scale = jax.lax.dynamic_update_slice(scale, s[None],
                                                 (layer, zero, zero, zero))
            return pool, scale
        return jax.lax.dynamic_update_slice(
            pool, x[None].astype(pool.dtype),
            (layer, zero, zero, zero, zero, zero))
    if ring and true_len is not None:
        # bucketed ring: the newest real page is traced, so a static trim
        # cannot find it — walk the newest ≤ NP REAL source pages via
        # traced indices (min(NP, n_pad) writes, not one per bucket page)
        return _fill_ring_dyn(pool, kv_seq, layer, true_len, scale=scale,
                              kv_quant=kv_quant)
    page0 = 0
    if ring:
        # statically drop source pages that can only be overwritten: the
        # ring keeps the newest NP pages, so start the "chunk" there
        page0 = max(0, ceil_div(S, T) - NP)
        kv_seq = kv_seq[:, page0 * T:]
    valid_len = jnp.asarray(kv_seq.shape[1], jnp.int32)
    fill = fill_chunk_window_at if ring else fill_chunk_global_at
    return fill(pool, kv_seq, layer, None,
                jnp.asarray(page0, jnp.int32), valid_len,
                scale=scale, kv_quant=kv_quant)


def _fill_ring_dyn(pool, kv_seq, layer, true_len, *, scale=None,
                   kv_quant: str = "none"):
    """Ring-fill ONE layer when only `true_len` of kv_seq's S tokens are
    real (bucket padding beyond).  Walks the NEWEST ≤ NP real source
    pages via traced indices so padding pages never evict live ones and
    the write count stays min(NP, n_pad)."""
    B, S, K, dh = kv_seq.shape
    NP, Ts = pool.shape[3], pool.shape[4]
    T = Ts * (2 if kv_quant == "kv4" else 1)
    x = _paged_from_seq(kv_seq, T)                 # [B, K, n_pad, T, dh]
    n_pad = x.shape[2]
    if kv_quant != "none":
        x, s_all = quant.quantize_kv_page(x, kv_quant)
    true_len = jnp.asarray(true_len, jnp.int32)
    n_src = (true_len + T - 1) // T
    zero = jnp.zeros((), jnp.int32)
    for r in range(min(NP, n_pad)):                # static trip count
        sp = n_src - 1 - r                         # traced source page
        ok = sp >= 0
        spc = jnp.clip(sp, 0, n_pad - 1)
        page = jax.lax.dynamic_slice_in_dim(x, spc, 1, axis=2)  # [B,K,1,*]
        phys = spc % NP
        pidx = (layer, zero, zero, phys, zero, zero)
        cur = jax.lax.dynamic_slice(pool, pidx, (1, B, K, 1, Ts, dh))
        upd = jnp.where(ok, page[None].astype(pool.dtype), cur)
        pool = jax.lax.dynamic_update_slice(pool, upd, pidx)
        if kv_quant != "none":
            sidx = (layer, zero, zero, phys)
            s_pg = jax.lax.dynamic_slice_in_dim(s_all, spc, 1, axis=2)
            cur_s = jax.lax.dynamic_slice(scale, sidx, (1, B, K, 1))
            scale = jax.lax.dynamic_update_slice(
                scale, jnp.where(ok, s_pg[None], cur_s), sidx)
    if kv_quant != "none":
        return pool, scale
    return pool


def _fill_layer_shared(pool, kv_seq, layer, table, *, ring: bool,
                       true_len=None, scale=None, kv_quant: str = "none"):
    """`fill_layer` for the shared pool: pages scatter through the table.

    pool: [L, K, P, Ts, dh]; table: [B, NP] physical ids; writes whose
    logical page holds no real token are redirected past P and dropped.
    """
    L, K, P, Ts, dh = pool.shape
    T = Ts * (2 if kv_quant == "kv4" else 1)
    B, S = kv_seq.shape[:2]
    NP = table.shape[1]
    x = _paged_from_seq(kv_seq, T)                 # [B, K, n_src, Ts, dh]
    if kv_quant != "none":
        x, s_all = quant.quantize_kv_page(x, kv_quant)
    n_src = x.shape[2]
    valid_len = jnp.asarray(S if true_len is None else true_len, jnp.int32)
    # NB: `layer` (traced scalar) and `phys` are NON-adjacent advanced
    # indices, so the scatter result dims are [*phys.shape, K, ...]
    if not ring:
        n_w = min(n_src, NP)
        ok = (jnp.arange(n_w, dtype=jnp.int32) * T) < valid_len   # [n_w]
        phys = jnp.where(ok[None], table[:, :n_w], P)             # [B, n_w]
        pool = pool.at[layer, :, phys].set(
            x[:, :, :n_w].transpose(0, 2, 1, 3, 4).astype(pool.dtype),
            mode="drop")
        if kv_quant != "none":
            scale = scale.at[layer, :, phys].set(
                s_all[:, :, :n_w].transpose(0, 2, 1), mode="drop")
            return pool, scale
        return pool
    # ring: ascending source pages so each ring slot keeps its newest
    # valid occupant (exactly the chunk-fill ordering); with an exact
    # length the oldest n_src - NP pages can only be overwritten — skip
    # them statically
    sp0 = max(0, n_src - NP) if true_len is None else 0
    for sp in range(sp0, n_src):                   # static trip count
        ok = (sp * T) < valid_len
        phys = jnp.where(ok, table[:, sp % NP], P)                # [B]
        pool = pool.at[layer, :, phys].set(
            x[:, :, sp].astype(pool.dtype), mode="drop")
        if kv_quant != "none":
            scale = scale.at[layer, :, phys].set(
                s_all[:, :, sp], mode="drop")
    if kv_quant != "none":
        return pool, scale
    return pool


# ---------------------------------------------------------------------------
# Chunked-prefill fills: one slot's page-aligned chunk into the SHARED pool
# ---------------------------------------------------------------------------
#
# The interleaved scheduler prefills each admitted prompt chunk-by-chunk
# straight into its slot's stripe of the batch pool (no one-sequence
# side cache, no splice copy).  Chunk starts are page-aligned, so every
# write lands on whole pages; the chunk's first token occupies physical
# page `page0` (the prefill page table is identity, logical == physical).
# Only pages holding at least one of the chunk's `valid_len` real tokens
# are written — bucket-padding pages are skipped, and a page index past
# the stripe is dropped rather than clamped into a live page.

def _fill_chunk_pages(pool, kv_chunk, layer, slot, page_of, valid_of, *,
                      scale, kv_quant: str):
    """Shared chunk-fill body: paginate (+quantize), then one guarded
    `dynamic_update_slice` of page (+scale) per chunk page.

    page_of(sp) -> traced physical page index (already in range);
    valid_of(sp) -> traced bool, False drops the write (keeps `cur`).
    slot=None writes EVERY batch row at the same page coordinates (the
    one-shot `fill_layer` path: a prefill is one whole-prompt chunk).
    A 5-D pool ([L, K, P, Ts, dh]) is the SHARED layout: page_of must
    then return table-translated GLOBAL physical indices, and `slot` is
    meaningless (the table row already names the slot's pages).
    """
    shared = pool.ndim == 5
    Bc, C, K, dh = kv_chunk.shape
    Ts = pool.shape[-2]
    T = Ts * (2 if kv_quant == "kv4" else 1)
    x = _paged_from_seq(kv_chunk, T)               # [Bc, K, n_pages, Ts, dh]
    n_pages = x.shape[2]
    if kv_quant != "none":
        x, s_all = quant.quantize_kv_page(x, kv_quant)
    zero = jnp.zeros((), jnp.int32)
    if slot is None and not shared:
        assert Bc == pool.shape[1], (Bc, pool.shape)
        slot = zero
    for sp in range(n_pages):                      # static trip count
        gp = page_of(sp)
        ok = valid_of(sp)
        page = jax.lax.dynamic_slice_in_dim(x, sp, 1, axis=2)  # [Bc,K,1,*]
        if shared:
            pidx = (layer, zero, gp, zero, zero)
            blk = (1, K, 1, Ts, dh)
            upd = page[0][None]                    # [1, K, 1, Ts, dh]
        else:
            pidx = (layer, slot, zero, gp, zero, zero)
            blk = (1, Bc, K, 1, Ts, dh)
            upd = page[None]
        cur = jax.lax.dynamic_slice(pool, pidx, blk)
        pool = jax.lax.dynamic_update_slice(
            pool, jnp.where(ok, upd.astype(pool.dtype), cur), pidx)
        if kv_quant != "none":
            s_pg = jax.lax.dynamic_slice_in_dim(s_all, sp, 1, axis=2)
            if shared:
                sidx = (layer, zero, gp)
                sblk, s_upd = (1, K, 1), s_pg[0][None]
            else:
                sidx = (layer, slot, zero, gp)
                sblk, s_upd = (1, Bc, K, 1), s_pg[None]
            cur_s = jax.lax.dynamic_slice(scale, sidx, sblk)
            scale = jax.lax.dynamic_update_slice(
                scale, jnp.where(ok, s_upd, cur_s), sidx)
    if kv_quant != "none":
        return pool, scale
    return pool


def fill_chunk_global_at(pool, kv_chunk, layer, slot, page0, valid_len, *,
                         scale=None, kv_quant: str = "none"):
    """Write one slot's prompt chunk into its stripe of the global pool.

    pool: [L, B, K, NP, Ts, dh] (in-place carry); kv_chunk: [1, C, K, dh];
    layer/slot/page0/valid_len: traced scalars.  A write past the stripe
    is dropped, never clamped into a live page.  Quantized pools (kv8/kv4)
    quantize whole pages exactly as `fill_prefill_at_quant`, so a page
    produced chunk-by-chunk is bit-identical to the one-shot fill's page.
    Returns pool, or (pool, scale) when quantized.
    """
    NP, T = pool.shape[3], pool.shape[4] * (2 if kv_quant == "kv4" else 1)
    return _fill_chunk_pages(
        pool, kv_chunk, layer, slot,
        lambda sp: jnp.clip(page0 + sp, 0, NP - 1),
        lambda sp: (sp * T < valid_len) & (page0 + sp < NP),
        scale=scale, kv_quant=kv_quant)


def fill_chunk_window_at(pool, kv_chunk, layer, slot, page0, valid_len, *,
                         scale=None, kv_quant: str = "none"):
    """Ring variant of `fill_chunk_global_at` for the window pool.

    Chunk page `page0 + sp` lands in ring slot `(page0 + sp) % NP`.
    Page-aligned chunk starts mean every global page is written exactly
    once across the whole prefill; when the chunk spans more pages than
    the ring, ascending order + the valid-page guard leave each ring slot
    holding its NEWEST valid occupant (a trailing padding page must not
    shadow the valid page `NP` positions older).  Base positions are
    derived by the engine (`window_page_positions_dyn`), not here.
    """
    NP, T = pool.shape[3], pool.shape[4] * (2 if kv_quant == "kv4" else 1)
    return _fill_chunk_pages(
        pool, kv_chunk, layer, slot,
        lambda sp: (page0 + sp) % NP,
        lambda sp: sp * T < valid_len,
        scale=scale, kv_quant=kv_quant)


# ---------------------------------------------------------------------------
# Shared-pool write paths: all coordinates go through the page table
# ---------------------------------------------------------------------------
#
# Pools are [L, K, P, Ts, dh] (+ scales [L, K, P]); the per-slot page
# tables hold GLOBAL physical indices handed out by the host allocator
# (`core/page_alloc.py`).  A table entry equal to P (one past the pool) is
# the engine's drop sentinel: scatters with mode="drop" discard the write,
# so inactive slots and unallocated logical pages can never corrupt a
# page another sequence owns.

def append_global_shared(pool, layer, phys, slot, val):
    """Ragged one-token append into a shared stacked pool.

    pool: [L, K, P, Ts, dh]; phys/slot: [B] per-sequence physical page and
    in-page slot; val: [B, K, dh].  phys >= P drops the write.  One
    in-place write per sequence (`_put_tokens`), so the pool keeps its
    layout.
    """
    zero = jnp.zeros((), jnp.int32)
    return _put_tokens(
        pool, val[:, None, :, None, None, :], phys, slot,
        lambda b, ph, sl: (layer, zero, ph, sl, zero))


def append_token_quant_shared(pool, scale, layer, phys, slot, val,
                              fmt: str):
    """Ragged requantizing append into a shared quantized pool.

    Gathers each sequence's touched page [K, Ts, dh] from the pool,
    dequantizes with its scale, inserts the token, zeros dead slots,
    requantizes, scatters page + scale back (O(page) per layer, exactly
    the stripe-layout `append_token_quant` through one indirection).
    """
    L, K, P, Ts, dh = pool.shape
    B = phys.shape[0]
    qpage = pool[layer, :, phys]                   # [B, K, Ts, dh] (clipped
    s = scale[layer, :, phys]                      # [B, K]  gather for the
    page = quant.dequantize_kv_page(qpage, s, fmt)  # dropped sentinel rows)
    b_idx = jnp.arange(B)
    page = page.at[b_idx, :, slot].set(val.astype(page.dtype))
    T = page.shape[-2]
    live = jnp.arange(T)[None, :] <= slot[:, None]             # [B, T]
    page = jnp.where(live[:, None, :, None], page, 0.0)
    q2, s2 = quant.quantize_kv_page(page, fmt)
    pool = pool.at[layer, :, phys].set(q2, mode="drop")
    scale = scale.at[layer, :, phys].set(s2, mode="drop")
    return pool, scale


def fill_chunk_global_at_shared(pool, kv_chunk, layer, table_row, page0,
                                valid_len, *, scale=None,
                                kv_quant: str = "none"):
    """Shared-pool `fill_chunk_global_at`: logical chunk page page0+sp
    resolves through ``table_row`` [NP] to its pool page (same writer
    body — `_fill_chunk_pages` detects the 5-D shared layout)."""
    NP = table_row.shape[0]
    T = pool.shape[3] * (2 if kv_quant == "kv4" else 1)
    return _fill_chunk_pages(
        pool, kv_chunk, layer, None,
        lambda sp: table_row[jnp.clip(page0 + sp, 0, NP - 1)],
        lambda sp: (sp * T < valid_len) & (page0 + sp < NP),
        scale=scale, kv_quant=kv_quant)


def fill_chunk_window_at_shared(pool, kv_chunk, layer, table_row, page0,
                                valid_len, *, scale=None,
                                kv_quant: str = "none"):
    """Shared-pool ring chunk fill: ring slot (page0+sp) % NP resolves
    through ``table_row`` [NPw]."""
    NP = table_row.shape[0]
    T = pool.shape[3] * (2 if kv_quant == "kv4" else 1)
    return _fill_chunk_pages(
        pool, kv_chunk, layer, None,
        lambda sp: table_row[(page0 + sp) % NP],
        lambda sp: sp * T < valid_len,
        scale=scale, kv_quant=kv_quant)


# ---------------------------------------------------------------------------
# Speculative-decode span appends (multi-token, accept-gated)
# ---------------------------------------------------------------------------
#
# `KVNANDEngine.verify_step` scores a k+1-token span in one forward pass
# and only then learns how many drafts were accepted.  The span writers
# below append UP TO S tokens per sequence in page order, but every write
# is gated per (sequence, span-position): the engine redirects the
# physical page index of a rejected (or inactive-slot) position to the
# pool's drop sentinel, so rejected drafts never reach a page.  That IS
# the rollback for every layout — nothing stale to undo:
#
#   * f32 pools: no write happened, so no stale bytes sit beyond `lengths`
#     waiting to inflate anything;
#   * kv8/kv4 pools: each accepted token replays `append_token_quant`'s
#     exact page chain (dequant → insert → zero dead slots → requant), so
#     the page codes and scales match what sequential decode would have
#     produced — a rejected draft never enters a page's amax;
#   * window rings: ring base positions advance only for pages that
#     received an accepted token (the engine derives them from the same
#     gate);
#   * shared pools: writes go through the slot's table row; the HOST half
#     of the rollback (returning speculatively allocated pages to
#     `core.page_alloc.PageAllocator` with refcounts and reservations
#     intact) lives in `serving/scheduler.py`.
#
# phys/slot: [S, B] per-span-position page coordinates (already gated —
# out-of-range phys drops); vals: [B, S, K, dh] span K or V.

def append_span(pool, layer, phys, slot, vals):
    """Ragged multi-token append into a stacked stripe pool.

    pool: [L, B, K, NP, T, dh]; the S span positions land in sequence
    order, so the page chain equals S sequential `decode_step` appends.
    """
    B = vals.shape[0]
    b_idx = jnp.arange(B)
    for s in range(vals.shape[1]):
        pool = pool.at[layer, b_idx, :, phys[s], slot[s]].set(
            vals[:, s].astype(pool.dtype), mode="drop")
    return pool


def append_span_shared(pool, layer, phys, slot, vals):
    """`append_span` for a shared pool [L, K, P, T, dh] (table-translated
    physical indices; the drop sentinel is P)."""
    for s in range(vals.shape[1]):
        pool = append_global_shared(pool, layer, phys[s], slot[s],
                                    vals[:, s])
    return pool


def append_span_quant(pool, scale, layer, phys, slot, vals, fmt: str):
    """Requantizing span append: one `append_token_quant` per span
    position, reproducing sequential decode's page chain bit-for-bit
    for the accepted prefix."""
    for s in range(vals.shape[1]):
        pool, scale = append_token_quant(pool, scale, layer, phys[s],
                                         slot[s], vals[:, s], fmt)
    return pool, scale


def append_span_quant_shared(pool, scale, layer, phys, slot, vals,
                             fmt: str):
    """Shared-pool requantizing span append (see `append_span_quant`)."""
    for s in range(vals.shape[1]):
        pool, scale = append_token_quant_shared(pool, scale, layer,
                                                phys[s], slot[s],
                                                vals[:, s], fmt)
    return pool, scale


def copy_page_shared(pool, src, dst):
    """Copy one physical page src -> dst across ALL layers of a shared
    pool [L, K, P, ...] (COW: the new exclusive owner starts from the
    shared page's bytes; works for code pools and scale leaves alike)."""
    L, K = pool.shape[:2]
    tail = pool.shape[3:]
    zeros = (0,) * len(tail)
    page = jax.lax.dynamic_slice(
        pool, (0, 0, jnp.asarray(src, jnp.int32)) + zeros, (L, K, 1) + tail)
    return jax.lax.dynamic_update_slice(
        pool, page, (0, 0, jnp.asarray(dst, jnp.int32)) + zeros)


# ---------------------------------------------------------------------------
# Host-staging / slot-splice writers (every pool-leaf write lives here:
# kvlint rule KV004 rejects direct .at[].set / dynamic_update_slice on
# cache pool leaves anywhere outside this module — DESIGN.md §15)
# ---------------------------------------------------------------------------

def _put_tokens(pool, upd, phys, slot, start):
    """Write row b's token block upd[b] at start(b, phys[b], slot[b]),
    one in-place dynamic_update_slice per row.

    A scatter here would lower to whole-pool layout copies: XLA gives the
    scatter the layout it prefers and converts the pool into it and back.
    A dynamic_update_slice keeps the pool's layout.  It clamps where the
    scatter's mode="drop" discarded, so a row at the drop sentinel
    (phys >= the pool's page count) writes back the bytes already at the
    clamped place, read in the same chain: it changes no page."""
    n_pages = pool.shape[-3]
    upd = upd.astype(pool.dtype)
    for b in range(upd.shape[0]):
        at = tuple(jnp.asarray(i, jnp.int32)
                   for i in start(b, phys[b], slot[b]))
        old = jax.lax.dynamic_slice(pool, at, upd.shape[1:])
        pool = jax.lax.dynamic_update_slice(
            pool, jnp.where(phys[b] < n_pages, upd[b], old), at)
    return pool


def append_token_inplace(pool, layer, phys, slot, val, *,
                         uniform_lengths: bool = False):
    """pool: [L, B, K, NP, T, dh]; write one token's K or V in place.

    Uniform-length fast path: all sequences advance in lockstep (static
    decode batching — every dry-run cell), so the append is ONE
    dynamic_update_slice.  The ragged path (continuous batching) writes
    each sequence's [K, dh] token with its own dynamic_update_slice at
    (layer, b, 0, phys, slot, 0), and phys >= NP drops the write
    (`_put_tokens`): the pool keeps the layout it has, so the paged
    kernel can read it in place.
    """
    if uniform_lengths:
        upd = val[None, :, :, None, None, :].astype(pool.dtype)
        zero = jnp.zeros((), jnp.int32)
        return jax.lax.dynamic_update_slice(
            pool, upd, (layer, zero, zero, phys[0], slot[0], zero))
    zero = jnp.zeros((), jnp.int32)
    return _put_tokens(
        pool, val[:, None, None, :, None, None, :], phys, slot,
        lambda b, ph, sl: (layer, b, zero, ph, sl, zero))


def stage_hot_slot(cache: "DecodeCache", slot, vals) -> "DecodeCache":
    """Tiered staging (DESIGN.md §13): write a promoted page's bytes into
    its freshly bound hot slot — one dynamic_update_slice per pool leaf
    named in `vals` ({leaf name: [L, K, T, dh] host bytes}).  Jit with a
    donated `cache` so the upload lands in place.

    Migration import (DESIGN.md §16) reuses this writer with `slot` as a
    flat-pool PHYSICAL page index (same page axis 2 on every shared-pool
    leaf, global and window alike), so a KVEnvelope's page bytes splice
    into a decode replica's pool through the one staging path."""
    upd = {}
    for name, val in vals.items():
        leaf = getattr(cache, name)
        v = jnp.expand_dims(val, 2).astype(leaf.dtype)
        start = tuple(slot if d == 2 else 0 for d in range(leaf.ndim))
        upd[name] = jax.lax.dynamic_update_slice(leaf, v, start)
    return dataclasses.replace(cache, **upd)


# leaves whose batch axis is axis 0 (tables / ring positions / lengths);
# pool data leaves carry the stacked-layer axis first
_BATCH_AXIS0 = ("page_table_g", "page_table_w", "page_pos_w", "lengths")


def import_slot_rows(cache: "DecodeCache", i, rows) -> "DecodeCache":
    """Migration import (DESIGN.md §16): write one slot's per-sequence
    rows into slot i of the batch cache — the `lengths` scalar, the
    `page_pos_w` ring-base row, and recurrent-state stacks ([L, ...]
    per-layer rows) named in `rows`.  The page-byte half of a KVEnvelope
    import goes through `stage_hot_slot`; together they keep every
    migration splice inside this module (KV004).  Jit with a donated
    `cache` so the rows land in place."""
    upd = {}
    for name, val in rows.items():
        leaf = getattr(cache, name)
        v = jnp.asarray(val).astype(leaf.dtype)
        if name in _BATCH_AXIS0:
            upd[name] = leaf.at[i].set(v)
        else:
            upd[name] = leaf.at[:, i].set(v)
    return dataclasses.replace(cache, **upd)


def splice_slot(cache: "DecodeCache", one: "DecodeCache",
                i) -> "DecodeCache":
    """Copy sequence 0 of a B=1 cache into slot i of the batch cache.

    One `dynamic_update_slice` per leaf: `one` already has a size-1 batch
    dim, so the update writes exactly the slot's stripe.  Jit this with a
    donated `cache` so XLA updates the pools in place instead of copying
    the whole pool per admit.
    """
    updates = {}
    for f in dataclasses.fields(cache):
        cur, new = getattr(cache, f.name), getattr(one, f.name)
        if cur is None:
            continue
        # batch axis position: leaf layouts are [L, B, ...] or [B, ...]
        ax = 0 if f.name in _BATCH_AXIS0 else 1
        start = tuple(jnp.asarray(i if d == ax else 0, jnp.int32)
                      for d in range(cur.ndim))
        updates[f.name] = jax.lax.dynamic_update_slice(
            cur, new.astype(cur.dtype), start)
    return dataclasses.replace(cache, **updates)


def splice_slot_ref(cache: "DecodeCache", one: "DecodeCache",
                    i: int) -> "DecodeCache":
    """Eager reference splice (the old O(pool) path) — kept for tests."""
    updates = {}
    for f in dataclasses.fields(cache):
        cur, new = getattr(cache, f.name), getattr(one, f.name)
        if cur is None:
            continue
        if f.name in _BATCH_AXIS0:
            updates[f.name] = cur.at[i].set(new[0])
        else:
            updates[f.name] = cur.at[:, i].set(new[:, 0])
    return dataclasses.replace(cache, **updates)
