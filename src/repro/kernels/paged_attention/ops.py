"""Public wrappers for paged attention: impl dispatch + split-page walk.

Both entry points — the decode partial and the multi-token chunk partial —
accept a `partitions` axis (paper §IV-B head-group parallelism × §IV-D
page-level mapping: independent partition walks whose partials the NPU
aggregates).  The page walk splits into `partitions` contiguous page
ranges, each producing a locally-normalized `(ō, m, ℓ)` partial, and the
partials recombine through the one N-partial merge core
(`merge.merge_partials`).  In the jnp ref path the split is a scanned
blocked walk — each partition's score tensor and dequantized pages stay
1/P-sized and cache-resident, which is where the CPU decode win at long
context comes from (see BENCH_kernels.json `kernels/paged_attention_100k`).
In the Pallas path the split is a real grid axis (kernel.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas, paged_attention_pallas_shared)
from repro.kernels.paged_attention.merge import (merge_partials,
                                                resolve_partitions)
from repro.kernels.paged_attention.ref import (gather_table_pages,
                                               paged_attention_partial_ref,
                                               paged_chunk_attention_ref)

VALID_IMPLS = ("auto", "ref", "pallas", "interpret")


def default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _check_impl(impl: str) -> None:
    if impl not in VALID_IMPLS:
        raise ValueError(f"unknown attention impl {impl!r}; "
                         f"expected one of {VALID_IMPLS}")


def _partition_walk(num_pages: int, partitions: int, piece):
    """Scan `piece(page_lo, pages_per_partition)` over contiguous page
    ranges and merge the stacked partials.  A scan (not a vmap) is
    deliberate: partitions evaluate one at a time, so each partition's
    intermediates are bounded at 1/P of the monolithic walk's."""
    npp = num_pages // partitions

    def body(carry, i):
        return carry, piece(i * npp, npp)

    _, (o, m, l) = jax.lax.scan(body, 0, jnp.arange(partitions))
    return merge_partials(o, m, l, axis=0)


def _resolve_ppb(pages_per_block: int, num_pages: int) -> int:
    """Largest power-of-two-halving of the request that divides the walk.

    Degrading to single-page blocks is never silent: a request for real
    blocking (ppb > 1) against a page count with no even divisor raises,
    instead of quietly serializing the kernel one page at a time."""
    want = min(pages_per_block, num_pages)
    ppb = want
    while num_pages % ppb:
        ppb //= 2
    if ppb < 1:
        ppb = 1
    if ppb == 1 and want > 1 and num_pages > 1:
        raise ValueError(
            f"pages_per_block={pages_per_block} cannot block a walk of "
            f"{num_pages} pages ({num_pages} has no power-of-two divisor "
            f"<= {want}); pass pages_per_block=1 explicitly for "
            "single-page blocks, or page-align the context length")
    return ppb


def paged_chunk_attention(q, k_pages, v_pages, page_base, start, q_pos, *,
                          window: Optional[int] = None, impl: str = "auto",
                          kv_quant: str = "none", k_scale=None,
                          v_scale=None, page_table=None,
                          partitions: int = 0):
    """Impl dispatch for the past-context partial of a multi-token span.

    Serves both chunked prefill (scalar `start`, `q_pos` [S]) and
    speculative-decode verification (per-row `start` [B], `q_pos`
    [B, S] — every slot of the decode batch sits at its own length).
    Mirrors `paged_attention_partial` so `EngineConfig.attn_impl` stays
    authoritative for both partials.  Unknown impl strings raise; every
    known impl — there is no Pallas chunk kernel yet (the natural
    follow-up) — lowers to the partitioned jnp walk: `partitions`
    contiguous page ranges scored independently and merged through
    `merge_partials`, so the per-partition score tensor is
    O(S·NP·T / partitions) instead of the monolithic O(S·NP·T).

    page_table: [B, NP] shared-pool indirection — k/v_pages (and scales)
    are then the GLOBAL [K, P_total, ...] pool and each partition gathers
    only its own table slice (1/P of the stripe) before the oracle runs.
    """
    _check_impl(impl)
    shared = page_table is not None
    NP = page_table.shape[1] if shared else k_pages.shape[2]
    P = resolve_partitions(partitions, NP)

    def piece(lo, npp):
        sl = lambda a, axis: jax.lax.dynamic_slice_in_dim(a, lo, npp, axis)
        if shared:
            tbl = sl(page_table, 1)
            kp = gather_table_pages(k_pages, tbl)
            vp = gather_table_pages(v_pages, tbl)
            ks = vs = None
            if kv_quant != "none":
                ks = gather_table_pages(k_scale, tbl)
                vs = gather_table_pages(v_scale, tbl)
        else:
            kp, vp = sl(k_pages, 2), sl(v_pages, 2)
            ks = None if k_scale is None else sl(k_scale, 2)
            vs = None if v_scale is None else sl(v_scale, 2)
        return paged_chunk_attention_ref(
            q, kp, vp, sl(page_base, 1), start, q_pos, window=window,
            kv_quant=kv_quant, k_scale=ks, v_scale=vs)

    if P == 1:
        return piece(0, NP)
    return _partition_walk(NP, P, piece)


def paged_attention_partial(
    q: jax.Array,          # [B, H, dh]
    k_pages: jax.Array,    # [B, K, NP, T, dh] (kv4: packed [B, K, NP, T/2, dh])
    v_pages: jax.Array,
    page_base: jax.Array,  # [B, NP]
    length: jax.Array,     # [B]
    *,
    window: Optional[int] = None,
    is_global=None,
    impl: str = "auto",
    pages_per_block: int = 8,
    kv_quant: str = "none",
    k_scale: Optional[jax.Array] = None,   # [B, K, NP] per-page×head scales
    v_scale: Optional[jax.Array] = None,
    page_table: Optional[jax.Array] = None,  # [B, NP] shared-pool tables
    partitions: int = 0,   # 0 = auto from page count; must divide NP
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (ō [B,H,dh] locally normalized, m [B,H], ℓ [B,H]).

    With `page_table`, k/v_pages (and scales) are the shared GLOBAL pool
    [K, P_total, ...]: the ref path gathers the slot's stripe view through
    the table; the Pallas path scalar-prefetches the table and lets the
    block index map address the P_total axis directly (no gather).

    `partitions` splits the page walk into that many contiguous ranges
    merged via `merge_partials` (0 resolves per `resolve_partitions`):
    the ref path scans them (1/P-bounded intermediates), the Pallas path
    runs them as a parallel grid axis per kv-head group.
    """
    _check_impl(impl)
    if impl == "auto":
        # dynamic local/global flags (scanned layers) have no kernel
        impl = "ref" if is_global is not None else default_impl()
    elif is_global is not None and impl != "ref":
        raise ValueError(
            f"impl={impl!r}: the paged kernels take a static window; "
            "dynamic per-layer flags (is_global) run only with impl='ref' "
            "or 'auto'")
    B, H, dh = q.shape
    shared = page_table is not None
    K = k_pages.shape[0] if shared else k_pages.shape[1]
    G = H // K
    NP = page_table.shape[1] if shared else k_pages.shape[2]
    P = resolve_partitions(partitions, NP)

    if impl == "ref":
        def piece(lo, npp):
            sl = lambda a, axis: jax.lax.dynamic_slice_in_dim(a, lo, npp,
                                                              axis)
            if shared:
                tbl = sl(page_table, 1)
                kp = gather_table_pages(k_pages, tbl)
                vp = gather_table_pages(v_pages, tbl)
                ks = vs = None
                if kv_quant != "none":
                    ks = gather_table_pages(k_scale, tbl)
                    vs = gather_table_pages(v_scale, tbl)
            else:
                kp, vp = sl(k_pages, 2), sl(v_pages, 2)
                ks = None if k_scale is None else sl(k_scale, 2)
                vs = None if v_scale is None else sl(v_scale, 2)
            return paged_attention_partial_ref(
                q, kp, vp, sl(page_base, 1), length, window=window,
                is_global=is_global, kv_quant=kv_quant,
                k_scale=ks, v_scale=vs)

        if P == 1:
            return piece(0, NP)
        return _partition_walk(NP, P, piece)

    if shared:
        o, m, l = paged_attention_pallas_shared(
            q.reshape(B, K, G, dh), k_pages, v_pages,
            page_table.astype(jnp.int32), page_base.astype(jnp.int32),
            length.astype(jnp.int32), window=window,
            interpret=(impl == "interpret"),
            kv_quant=kv_quant, k_scale=k_scale, v_scale=v_scale,
            partitions=P)
        if P > 1:
            o, m, l = merge_partials(o, m, l, axis=2)
        return (o.reshape(B, H, dh).astype(q.dtype),
                m.reshape(B, H), l.reshape(B, H))

    ppb = _resolve_ppb(pages_per_block, NP // P)
    o, m, l = paged_attention_pallas(
        q.reshape(B, K, G, dh), k_pages, v_pages,
        page_base.astype(jnp.int32), length.astype(jnp.int32),
        window=window, pages_per_block=ppb,
        interpret=(impl == "interpret"),
        kv_quant=kv_quant, k_scale=k_scale, v_scale=v_scale,
        partitions=P)
    if P > 1:
        o, m, l = merge_partials(o, m, l, axis=2)
    return (o.reshape(B, H, dh).astype(q.dtype),
            m.reshape(B, H), l.reshape(B, H))
