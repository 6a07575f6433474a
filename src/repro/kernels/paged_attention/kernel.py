"""Pallas TPU paged decode-attention kernels (striped and shared pool).

Grid: (B, K, page_blocks) — page_blocks innermost/sequential so VMEM scratch
carries the online softmax across the slot's pages.  Each step streams one
block of whole pages HBM→VMEM and computes the G-query-head group against
them (the paper's head-group granule):

  * striped pool [B, K, NP, T, dh]: `pages_per_block` physically sequential
    pages per step (paper §IV-D: "sequential page order ... preserved for
    high read speed");
  * shared pool [K, P_total, T, dh]: one pool page per step, picked by the
    scalar-prefetched page table in the BLOCK INDEX MAP — the §IV-D
    logical→physical walk happens in SMEM before the DMA, never in the
    inner loop.

Either pool may also come whole, with its leading layer axis
([L, B, K, NP, T, dh] / [L, K, P_total, T, dh], the decode cache's own
leaves), beside a traced `layer` index.  The index is one more
scalar-prefetch operand that only the page index maps read: the layer
dim is squeezed out of the page block, so the body sees the same block
either way, and the decode step reads its layer's pages from the pool
in place instead of copying the layer out first.

page_base [B, NP] and length [B] arrive via scalar prefetch (SMEM): token
validity is data-derived, so there is no gather in the inner loop.  SMEM
yields scalars only, so per-page values (bases, kv8/kv4 scales) are read
one page at a time and broadcast onto that page's score columns.

Outputs are the per-shard partials (ō, m, ℓ) consumed by the cross-device
combine (core/seqpar.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _planes(ref, rows: int, dh: int, kv_quant: str):
    """VMEM page block -> list of [rows, dh] f32 code planes (unscaled).

    kv4 stores two tokens per byte along the token dim (high nibble = the
    even token, the `quant_gemv` packing order).  Mosaic cannot cast the
    nibble planes back into interleaved token order, so each plane is
    scored as its own group of columns — the online softmax is
    order-free.  The unpack happens in-register after the 2-4× smaller
    block has streamed HBM→VMEM — that is the whole win."""
    x = ref[0, 0].reshape(rows, dh)
    if kv_quant == "kv4":
        x = x.astype(jnp.int32)              # Mosaic shifts 32-bit lanes only
        return [(((x >> 4) & 0xF) - 8).astype(jnp.float32),
                ((x & 0xF) - 8).astype(jnp.float32)]
    return [x.astype(jnp.float32)]


def _slot_scales(scale: jax.Array, page_table=None) -> jax.Array:
    """Per-page scales as [B, K, 1, NP] f32 rows for an SMEM block.

    Mosaic tiles a block's last two dims by (8, 128) unless they equal the
    array's own, so a one-page (1, 1) scale block is refused.  A slot's
    whole row of page scales is one legal block instead: it is fetched once
    per (slot, kv-head) and the kernel reads each page's scale as an SMEM
    scalar.  Shared-pool scales [K, P_total] are first gathered through the
    page table (B·K·NP floats, ~1/(T·dh) of the pages the kernel reads)."""
    if page_table is not None:
        scale = jnp.take(scale, page_table, axis=1).transpose(1, 0, 2)
    return scale.astype(jnp.float32)[:, :, None, :]


def _kernel(base_ref, len_ref,                       # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref, *refs,              # blocks (+SMEM scales)
            ppb: int, n_blocks: int, window: Optional[int],
            scale: float, kv_quant: str, partitioned: bool):
    """One page block of the online softmax.  Partitioned grid
    (B, K, P, blocks-per-partition): each partition is an independent walk
    over its own page range — the scratch re-initializes at ITS first
    block and finalizes into ITS output slot, and `blk` addresses the
    global page-block axis."""
    if kv_quant == "none":
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    else:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    ib = pl.program_id(3 if partitioned else 2)
    blk = pl.program_id(2) * n_blocks + ib if partitioned else ib
    first = blk * ppb                                # first logical page

    @pl.when(ib == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    Ts, dh = k_ref.shape[-2], k_ref.shape[-1]
    rows = ppb * Ts
    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    page, slot = col // Ts, col % Ts

    def per_page(read, dtype):
        """read(logical page) of each column's page: an SMEM scalar."""
        if ppb == 1:
            return read(first)
        row = jnp.zeros((1, rows), dtype)
        for i in range(ppb):
            row = jnp.where(page == i, read(first + i), row)
        return row

    q = q_ref[0, 0].astype(jnp.float32) * scale              # [G, dh]
    k_planes = _planes(k_ref, rows, dh, kv_quant)
    v_planes = _planes(v_ref, rows, dh, kv_quant)
    length = len_ref[b]
    base = per_page(lambda p: base_ref[b, p], jnp.int32)
    # per-page × per-head dequant scales: the K scale folds into s AFTER
    # the MXU dot, the V scale folds into p BEFORE the attend dot — no
    # dequantized page copy ever materializes.
    if kv_quant != "none":
        k_cols = per_page(lambda p: ks_ref[0, 0, 0, p], jnp.float32)
        v_cols = per_page(lambda p: vs_ref[0, 0, 0, p], jnp.float32)

    scores, valids = [], []
    for j, kp in enumerate(k_planes):
        pos = base + slot * len(k_planes) + j                 # [1, rows]
        valid = (base >= 0) & (pos < length)
        if window is not None:
            valid &= pos > (length - 1 - window)
        s = jax.lax.dot_general(q, kp, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if kv_quant != "none":
            s = s * k_cols
        scores.append(jnp.where(valid, s, NEG_INF))           # [G, rows]
        valids.append(valid)

    m_prev = m_scr[...]                                       # [G, 1]
    m_new = m_prev
    for s in scores:
        m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * alpha
    acc = acc_scr[...] * alpha
    for s, valid, vp in zip(scores, valids, v_planes):
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = l_new + jnp.sum(p, -1, keepdims=True)
        if kv_quant != "none":
            p = p * v_cols
        acc = acc + jax.lax.dot_general(
            p, vp, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ib == n_blocks - 1)
    def _finalize():
        out = (0, 0, 0) if partitioned else (0, 0)
        ll = jnp.maximum(l_scr[...], 1e-30)
        o_ref[out] = (acc_scr[...] / ll).astype(o_ref.dtype)
        m_ref[out] = m_scr[...]
        l_ref[out] = l_scr[...]


def _index_only(kernel, n: int):
    """`kernel` behind n leading scalar-prefetch refs (layer index, page
    table) that only the index maps read."""
    def body(*refs):
        kernel(*refs[n:])
    return body


def _with_layer(layer, prefetch, page_block, page_index):
    """Prefetch, page block and page index map for a pool with or
    without its leading layer axis.  With a layer, the traced index is
    prefetched first and the squeezed (None) layer dim of the block is
    addressed by it, so the body's block is unchanged."""
    if layer is None:
        return prefetch, page_block, page_index
    lyr = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    return ((lyr,) + prefetch, (None,) + page_block,
            lambda b, k, blk, lyr_ref, *pf: (lyr_ref[0],)
            + page_index(b, k, blk, *pf))


def _paged_call(kernel, prefetch, q, k_pages, v_pages, scales, page_block,
                page_index, *, name, B, NP, n_blocks, partitions,
                interpret):
    """pallas_call `name` over grid (B, K[, partitions], n_blocks).

    page_index(b, k, blk, *prefetch_refs) is the page block's index for
    global page-block `blk`; partitions > 1 adds a PARALLEL partition axis
    whose per-partition partials land in [B, K, partitions, ...] outputs
    for the caller's `merge.merge_partials`.  `kernel` takes the scalar
    prefetch refs that the body reads (page_base, length) first."""
    K, G, dh = q.shape[1], q.shape[2], q.shape[3]
    if partitions == 1:
        grid = (B, K, n_blocks)
        pidx = lambda b, k, ib, *pf: page_index(b, k, ib, *pf)
        head = lambda b, k, ib, *_: (b, k, 0, 0)
        out_lead, out_idx = (B, K), lambda b, k, ib, *_: (b, k, 0, 0)
    else:
        grid = (B, K, partitions, n_blocks)
        pidx = lambda b, k, pt, ib, *pf: page_index(
            b, k, pt * n_blocks + ib, *pf)
        head = lambda b, k, pt, ib, *_: (b, k, 0, 0)
        out_lead = (B, K, partitions)
        out_idx = lambda b, k, pt, ib, *_: (b, k, pt, 0, 0)
    ones = (1,) * len(out_lead)
    in_specs = [pl.BlockSpec((1, 1, G, dh), head),
                pl.BlockSpec(page_block, pidx),
                pl.BlockSpec(page_block, pidx)]
    in_specs += [pl.BlockSpec((1, 1, 1, NP), head, memory_space=pltpu.SMEM)
                 for _ in scales]
    out_shape = [jax.ShapeDtypeStruct(out_lead + (G, w), jnp.float32)
                 for w in (dh, 1, 1)]
    out_specs = [pl.BlockSpec(ones + (G, w), out_idx) for w in (dh, 1, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, dh), jnp.float32),
        ],
    )
    semantics = ("parallel",) * (len(grid) - 1) + ("arbitrary",)
    o, m, l = pl.pallas_call(
        kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
    )(*prefetch, q, k_pages, v_pages, *scales)
    return o, m[..., 0], l[..., 0]


def paged_attention_pallas_shared(
    q: jax.Array,          # [B, K, G, dh]
    k_pages: jax.Array,    # [(L,) K, P_total, T, dh] (kv4: [.., T/2, dh])
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, NP] int32 physical indices (in range)
    page_base: jax.Array,  # [B, NP] absolute pos of slot 0 (<0 = unwritten)
    length: jax.Array,     # [B] int32
    *,
    window: Optional[int] = None,
    interpret: bool = False,
    kv_quant: str = "none",
    k_scale: Optional[jax.Array] = None,   # [K, P_total] f32
    v_scale: Optional[jax.Array] = None,
    partitions: int = 1,
    layer: Optional[jax.Array] = None,     # index into L (5-D pools)
):
    """Shared-pool paged decode attention: grid (B, K, NP) with the page
    table scalar-prefetched so the BLOCK INDEX MAP addresses the global
    P_total axis directly — one arbitrary pool page per step, no gathered
    copy of the slot's stripe ever materializes.

    partitions > 1 splits the logical page walk into a PARALLEL grid axis
    — grid (B, K, partitions, NP/partitions) — emitting per-partition
    partials [B, K, partitions, ...] for the caller to merge
    (`merge.merge_partials`); the sequential scratch accumulation then
    only spans one partition's pages (the paper's head-group × split-page
    parallel read, with NPU-side aggregation).

    With `layer`, k/v_pages are the whole stacked pool [L, K, P_total,
    T, dh] and the walk reads layer `layer` of it in place; scales stay
    per layer ([K, P_total])."""
    assert k_pages.ndim == (4 if layer is None else 5), (k_pages.shape,
                                                         layer)
    Ts, dh = k_pages.shape[-2:]
    B, NP = page_table.shape
    assert NP % partitions == 0, (NP, partitions)
    npp = NP // partitions
    table = page_table.astype(jnp.int32)
    scales = []
    if kv_quant != "none":
        assert k_scale is not None and v_scale is not None, kv_quant
        scales = [_slot_scales(k_scale, table), _slot_scales(v_scale, table)]
    prefetch, block, index = _with_layer(
        layer, (table, page_base, length), (1, 1, Ts, dh),
        lambda b, k, blk, tbl, *_: (k, tbl[b, blk], 0, 0))
    kernel = functools.partial(_kernel, ppb=1, n_blocks=npp, window=window,
                               scale=dh ** -0.5, kv_quant=kv_quant,
                               partitioned=(partitions > 1))
    return _paged_call(
        _index_only(kernel, len(prefetch) - 2), prefetch, q, k_pages,
        v_pages, scales, block, index,
        name="paged_attention_shared", B=B, NP=NP, n_blocks=npp,
        partitions=partitions, interpret=interpret)


def paged_attention_pallas(
    q: jax.Array,          # [B, K, G, dh]
    k_pages: jax.Array,    # [(L,) B, K, NP, T, dh] (kv4: [.., T/2, dh])
    v_pages: jax.Array,
    page_base: jax.Array,  # [B, NP] int32
    length: jax.Array,     # [B] int32
    *,
    window: Optional[int] = None,
    pages_per_block: int = 8,
    interpret: bool = False,
    kv_quant: str = "none",
    k_scale: Optional[jax.Array] = None,   # [B, K, NP] f32 per-page scales
    v_scale: Optional[jax.Array] = None,
    partitions: int = 1,
    layer: Optional[jax.Array] = None,     # index into L (6-D pools)
):
    """Sequence-striped paged decode attention.

    partitions > 1 turns the page-block walk into grid
    (B, K, partitions, blocks-per-partition): the block axis stays the
    sequential ("arbitrary") scratch-carrying dim but now only spans one
    partition's pages, while the partition axis is PARALLEL — each
    (kv-head, partition) pair is an independent walk whose partial lands
    in [B, K, partitions, ...] outputs for the caller's
    `merge.merge_partials`.

    With `layer`, k/v_pages are the whole stacked pool [L, B, K, NP, T,
    dh] and the walk reads layer `layer` of it in place; scales stay per
    layer ([B, K, NP])."""
    assert k_pages.ndim == (5 if layer is None else 6), (k_pages.shape,
                                                         layer)
    B, K, NP, Ts, dh = k_pages.shape[-5:]
    assert NP % partitions == 0, (NP, partitions)
    npp = NP // partitions
    ppb = min(pages_per_block, npp)
    assert npp % ppb == 0, (npp, ppb)
    scales = []
    if kv_quant != "none":
        assert k_scale is not None and v_scale is not None, kv_quant
        scales = [_slot_scales(k_scale), _slot_scales(v_scale)]
    prefetch, block, index = _with_layer(
        layer, (page_base, length), (1, 1, ppb, Ts, dh),
        lambda b, k, blk, *_: (b, k, blk, 0, 0))
    kernel = functools.partial(_kernel, ppb=ppb, n_blocks=npp // ppb,
                               window=window, scale=dh ** -0.5,
                               kv_quant=kv_quant,
                               partitioned=(partitions > 1))
    return _paged_call(
        _index_only(kernel, len(prefetch) - 2), prefetch, q, k_pages,
        v_pages, scales, block, index,
        name="paged_attention", B=B, NP=NP, n_blocks=npp // ppb,
        partitions=partitions, interpret=interpret)
