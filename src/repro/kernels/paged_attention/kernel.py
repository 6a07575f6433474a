"""Pallas TPU paged decode-attention kernels (striped and shared pool).

Grid: (B, K/Kh, page_blocks) — page_blocks innermost/sequential so VMEM
scratch carries the online softmax across the slot's pages.  Each step
streams one block of whole pages of Kh kv-heads HBM→VMEM and scores each
head's G-query-head group against them (the paper's head-group granule).
A step costs about the same whatever it moves, so Kh is all K heads of the
row unless their double-buffered K and V blocks pass `KV_BLOCK_VMEM`:

  * striped pool [B, K, NP, T, dh]: `pages_per_block` physically sequential
    pages per step (paper §IV-D: "sequential page order ... preserved for
    high read speed");
  * shared pool [K, P_total, T, dh]: one pool page per step, picked by the
    scalar-prefetched page table in the BLOCK INDEX MAP — the §IV-D
    logical→physical walk happens in SMEM before the DMA, never in the
    inner loop.

Dead blocks are neither fetched nor scored: a scalar-prefetched [B] count
of each row's live page blocks (`_live_blocks`) clamps the page index map
at the row's last live block — consecutive steps that name the same block
issue no DMA — and the body runs only below that count.

Either pool may also come whole, with its leading layer axis
([L, B, K, NP, T, dh] / [L, K, P_total, T, dh], the decode cache's own
leaves), beside a traced `layer` index.  The index is one more
scalar-prefetch operand that only the page index maps read: the layer
dim is squeezed out of the page block, so the body sees the same block
either way, and the decode step reads its layer's pages from the pool
in place instead of copying the layer out first.

page_base [B, NP], length [B] and the live counts arrive via scalar
prefetch (SMEM): token validity is data-derived, so there is no gather in
the inner loop.  SMEM yields scalars only, so per-page values (bases, kv8/kv4 scales) are read
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
# VMEM a step's double-buffered K and V page blocks may take (of v5e's
# 16 MiB default scoped limit; the body's f32 planes need the rest)
KV_BLOCK_VMEM = 4 << 20


def _planes(x, rows: int, dh: int, kv_quant: str):
    """One head's VMEM page block -> list of [rows, dh] f32 code planes
    (unscaled).

    kv4 stores two tokens per byte along the token dim (high nibble = the
    even token, the `quant_gemv` packing order).  Mosaic cannot cast the
    nibble planes back into interleaved token order, so each plane is
    scored as its own group of columns — the online softmax is
    order-free.  The unpack happens in-register after the 2-4× smaller
    block has streamed HBM→VMEM — that is the whole win."""
    x = x.reshape(rows, dh)
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
    per (slot, kv-head group) and the kernel reads each page's scale as an
    SMEM scalar.  Shared-pool scales [K, P_total] are first gathered through
    the page table (B·K·NP floats, ~1/(T·dh) of the pages the kernel
    reads)."""
    if page_table is not None:
        scale = jnp.take(scale, page_table, axis=1).transpose(1, 0, 2)
    return scale.astype(jnp.float32)[:, :, None, :]


def _heads_per_step(K: int, head_block_bytes: int, budget: int) -> int:
    """Kh: the most of a row's K kv-heads whose K and V page blocks,
    double-buffered, fit `budget` bytes of VMEM — K itself when they fit,
    else its largest divisor that does (at least 1)."""
    return max(d for d in range(1, K + 1)
               if K % d == 0 and (d == 1 or 4 * d * head_block_bytes
                                  <= budget))


def _live_blocks(page_base: jax.Array, length: jax.Array,
                 ppb: int) -> jax.Array:
    """[B] int32: each row's page blocks up to the last one holding a key
    the kernel could score (a page with base in [0, length)).  Read from
    the bases, so it follows any table: the first ceil(length / T) pages
    of a stripe or table row, a ring's written pages before it wraps and
    all of them after."""
    NP = page_base.shape[1]
    live = (page_base >= 0) & (page_base < length[:, None])
    ends = (jnp.arange(NP, dtype=jnp.int32) // ppb + 1)[None]
    return jnp.max(jnp.where(live, ends, 0), axis=1).astype(jnp.int32)


def _kernel(base_ref, len_ref, live_ref,             # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref, *refs,              # blocks (+SMEM scales)
            ppb: int, n_blocks: int, window: Optional[int],
            scale: float, kv_quant: str, partitioned: bool, shared: bool):
    """One page block of the online softmax, for each of the block's Kh
    kv-heads in turn.  Partitioned grid (B, K/Kh, P, blocks-per-partition):
    each partition is an independent walk over its own page range — the
    scratch re-initializes at ITS first block and finalizes into ITS
    output slot, and `blk` addresses the global page-block axis.  A block
    at or past the row's live count was never fetched (the index map
    repeated the last live block) and is not scored; a partition holding
    no live block finalizes as the empty partial (m = NEG_INF, ℓ = 0),
    the merge's identity."""
    if kv_quant == "none":
        o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    else:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = refs
    b = pl.program_id(0)
    ib = pl.program_id(3 if partitioned else 2)
    blk = pl.program_id(2) * n_blocks + ib if partitioned else ib
    first = blk * ppb                                # first logical page
    Kh = q_ref.shape[1]

    @pl.when(ib == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(blk < live_ref[b])
    def _step():
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

        length = len_ref[b]
        base = per_page(lambda p: base_ref[b, p], jnp.int32)
        n_planes = 2 if kv_quant == "kv4" else 1
        valids = []
        for j in range(n_planes):
            pos = base + slot * n_planes + j                  # [1, rows]
            valid = (base >= 0) & (pos < length)
            if window is not None:
                valid &= pos > (length - 1 - window)
            valids.append(valid)

        for h in range(Kh):
            at = (h, 0) if shared else (0, h)
            q = q_ref[0, h].astype(jnp.float32) * scale       # [G, dh]
            k_planes = _planes(k_ref[at], rows, dh, kv_quant)
            v_planes = _planes(v_ref[at], rows, dh, kv_quant)
            # per-page × per-head dequant scales: the K scale folds into s
            # AFTER the MXU dot, the V scale folds into p BEFORE the attend
            # dot — no dequantized page copy ever materializes.
            if kv_quant != "none":
                k_cols = per_page(lambda p: ks_ref[0, h, 0, p], jnp.float32)
                v_cols = per_page(lambda p: vs_ref[0, h, 0, p], jnp.float32)

            scores = []
            for kp, valid in zip(k_planes, valids):
                s = jax.lax.dot_general(q, kp, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if kv_quant != "none":
                    s = s * k_cols
                scores.append(jnp.where(valid, s, NEG_INF))   # [G, rows]

            m_prev = m_scr[h]                                 # [G, 1]
            m_new = m_prev
            for s in scores:
                m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[h] * alpha
            acc = acc_scr[h] * alpha
            for s, valid, vp in zip(scores, valids, v_planes):
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                l_new = l_new + jnp.sum(p, -1, keepdims=True)
                if kv_quant != "none":
                    p = p * v_cols
                acc = acc + jax.lax.dot_general(
                    p, vp, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            m_scr[h] = m_new
            l_scr[h] = l_new
            acc_scr[h] = acc

    @pl.when(ib == n_blocks - 1)
    def _finalize():
        for h in range(Kh):
            out = (0, h, 0) if partitioned else (0, h)
            ll = jnp.maximum(l_scr[h], 1e-30)
            o_ref[out] = (acc_scr[h] / ll).astype(o_ref.dtype)
            m_ref[out] = m_scr[h]
            l_ref[out] = l_scr[h]


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
            lambda b, g, blk, lyr_ref, *pf: (lyr_ref[0],)
            + page_index(b, g, blk, *pf))


def _paged_call(kernel, prefetch, q, k_pages, v_pages, scales, page_block,
                page_index, *, name, B, NP, Kh, n_blocks, partitions,
                interpret):
    """pallas_call `name` over grid (B, K/Kh[, partitions], n_blocks).

    page_index(b, g, blk, *prefetch_refs) is the page block's index for
    kv-head group `g` and global page-block `blk`; the last prefetch ref
    is the rows' live block counts, and a block at or past its row's
    count is addressed as the row's last live block, so its step issues
    no DMA.  partitions > 1 adds a PARALLEL partition axis whose
    per-partition partials land in [B, K, partitions, ...] outputs for
    the caller's `merge.merge_partials`.  `kernel` takes the scalar
    prefetch refs that the body reads (page_base, length, live) first."""
    K, G, dh = q.shape[1], q.shape[2], q.shape[3]

    def clamp(b, blk, pf):
        return jnp.minimum(blk, jnp.maximum(pf[-1][b] - 1, 0))

    if partitions == 1:
        grid = (B, K // Kh, n_blocks)
        pidx = lambda b, g, ib, *pf: page_index(b, g, clamp(b, ib, pf), *pf)
        head = lambda b, g, ib, *_: (b, g, 0, 0)
        out_lead, out_idx = (B, K), head
    else:
        grid = (B, K // Kh, partitions, n_blocks)
        pidx = lambda b, g, pt, ib, *pf: page_index(
            b, g, clamp(b, pt * n_blocks + ib, pf), *pf)
        head = lambda b, g, pt, ib, *_: (b, g, 0, 0)
        out_lead = (B, K, partitions)
        out_idx = lambda b, g, pt, ib, *_: (b, g, pt, 0, 0)
    out_block = (1, Kh) + (1,) * (len(out_lead) - 2)
    in_specs = [pl.BlockSpec((1, Kh, G, dh), head),
                pl.BlockSpec(page_block, pidx),
                pl.BlockSpec(page_block, pidx)]
    in_specs += [pl.BlockSpec((1, Kh, 1, NP), head, memory_space=pltpu.SMEM)
                 for _ in scales]
    out_shape = [jax.ShapeDtypeStruct(out_lead + (G, w), jnp.float32)
                 for w in (dh, 1, 1)]
    out_specs = [pl.BlockSpec(out_block + (G, w), out_idx)
                 for w in (dh, 1, 1)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Kh, G, 1), jnp.float32),
            pltpu.VMEM((Kh, G, 1), jnp.float32),
            pltpu.VMEM((Kh, G, dh), jnp.float32),
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
    kv_vmem_bytes: int = KV_BLOCK_VMEM,
):
    """Shared-pool paged decode attention: grid (B, K/Kh, NP) with the
    page table scalar-prefetched so the BLOCK INDEX MAP addresses the
    global P_total axis directly — one arbitrary pool page of Kh kv-heads
    per step, no gathered copy of the slot's stripe ever materializes.
    Each row's walk stops fetching after its last live page.

    partitions > 1 splits the logical page walk into a PARALLEL grid axis
    — grid (B, K/Kh, partitions, NP/partitions) — emitting per-partition
    partials [B, K, partitions, ...] for the caller to merge
    (`merge.merge_partials`); the sequential scratch accumulation then
    only spans one partition's pages (the paper's head-group × split-page
    parallel read, with NPU-side aggregation).

    With `layer`, k/v_pages are the whole stacked pool [L, K, P_total,
    T, dh] and the walk reads layer `layer` of it in place; scales stay
    per layer ([K, P_total]).  `kv_vmem_bytes` bounds the double-buffered
    K and V blocks that set Kh (`_heads_per_step`)."""
    assert k_pages.ndim == (4 if layer is None else 5), (k_pages.shape,
                                                         layer)
    K, _, Ts, dh = k_pages.shape[-4:]
    B, NP = page_table.shape
    assert NP % partitions == 0, (NP, partitions)
    npp = NP // partitions
    Kh = _heads_per_step(K, Ts * dh * k_pages.dtype.itemsize, kv_vmem_bytes)
    table = page_table.astype(jnp.int32)
    scales = []
    if kv_quant != "none":
        assert k_scale is not None and v_scale is not None, kv_quant
        scales = [_slot_scales(k_scale, table), _slot_scales(v_scale, table)]
    live = _live_blocks(page_base, length, 1)
    prefetch, block, index = _with_layer(
        layer, (table, page_base, length, live), (Kh, 1, Ts, dh),
        lambda b, g, blk, tbl, *_: (g, tbl[b, blk], 0, 0))
    kernel = functools.partial(_kernel, ppb=1, n_blocks=npp, window=window,
                               scale=dh ** -0.5, kv_quant=kv_quant,
                               partitioned=(partitions > 1), shared=True)
    return _paged_call(
        _index_only(kernel, len(prefetch) - 3), prefetch, q, k_pages,
        v_pages, scales, block, index,
        name="paged_attention_shared", B=B, NP=NP, Kh=Kh, n_blocks=npp,
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
    kv_vmem_bytes: int = KV_BLOCK_VMEM,
):
    """Sequence-striped paged decode attention: grid (B, K/Kh, NP/ppb),
    each step one block of `pages_per_block` pages of Kh kv-heads; each
    row's walk stops fetching after its last live block.

    partitions > 1 turns the page-block walk into grid
    (B, K/Kh, partitions, blocks-per-partition): the block axis stays the
    sequential ("arbitrary") scratch-carrying dim but now only spans one
    partition's pages, while the partition axis is PARALLEL — each
    (kv-head group, partition) pair is an independent walk whose partial
    lands in [B, K, partitions, ...] outputs for the caller's
    `merge.merge_partials`.

    With `layer`, k/v_pages are the whole stacked pool [L, B, K, NP, T,
    dh] and the walk reads layer `layer` of it in place; scales stay per
    layer ([B, K, NP]).  `kv_vmem_bytes` bounds the double-buffered K and
    V blocks that set Kh (`_heads_per_step`)."""
    assert k_pages.ndim == (5 if layer is None else 6), (k_pages.shape,
                                                         layer)
    B, K, NP, Ts, dh = k_pages.shape[-5:]
    assert NP % partitions == 0, (NP, partitions)
    npp = NP // partitions
    ppb = min(pages_per_block, npp)
    assert npp % ppb == 0, (npp, ppb)
    Kh = _heads_per_step(K, ppb * Ts * dh * k_pages.dtype.itemsize,
                         kv_vmem_bytes)
    scales = []
    if kv_quant != "none":
        assert k_scale is not None and v_scale is not None, kv_quant
        scales = [_slot_scales(k_scale), _slot_scales(v_scale)]
    live = _live_blocks(page_base, length, ppb)
    prefetch, block, index = _with_layer(
        layer, (page_base, length, live), (1, Kh, ppb, Ts, dh),
        lambda b, g, blk, *_: (b, g, blk, 0, 0))
    kernel = functools.partial(_kernel, ppb=ppb, n_blocks=npp // ppb,
                               window=window, scale=dh ** -0.5,
                               kv_quant=kv_quant,
                               partitioned=(partitions > 1), shared=False)
    return _paged_call(
        _index_only(kernel, len(prefetch) - 3), prefetch, q, k_pages,
        v_pages, scales, block, index,
        name="paged_attention", B=B, NP=NP, Kh=Kh, n_blocks=npp // ppb,
        partitions=partitions, interpret=interpret)
