"""Sequence-parallel attention: the paper's G2 dataflow on a TPU mesh.

KVNAND §IV-B: G2 dies each hold a slice of the KV cache, compute partial
K·q products, the NPU aggregates them for the softmax, and the dies apply
Attend to their local V slice.  That is precisely *flash-decoding* with a
log-sum-exp combine:

  decode : KV pages sharded over `model` (± `data`/`pod` for batch-1 long
           context); each device computes partial (ō, m, ℓ) over local pages;
           `combine_partials` (pmax/psum) plays the NPU-aggregation role.
  train / prefill : ring attention — Q/K/V sequence-sharded, KV blocks
           rotate via ppermute with online-softmax accumulation (SP).

Neither path ever constrains on head-count divisibility (20/25-head archs
shard fine on a 16-wide axis) and the KV bytes never cross the interconnect
— only q vectors and [B, H] statistics do, the paper's core bandwidth
insight.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.flash_attention.ref import NEG_INF


# ---------------------------------------------------------------------------
# Partial-attention merge (the "NPU softmax aggregation")
# ---------------------------------------------------------------------------

def merge_two(o1, m1, l1, o2, m2, l2):
    """Merge two locally-normalized partial attentions (log-sum-exp).

    Two-ary convenience over the N-partial merge core
    (`kernels.paged_attention.merge_partials`) — same math, same
    empty-partition identity."""
    from repro.kernels.paged_attention.merge import merge_partials
    return merge_partials(jnp.stack([o1, o2]), jnp.stack([m1, m2]),
                          jnp.stack([l1, l2]), axis=0)


def combine_partials(o, m, l, axis_names: Sequence[str]):
    """Cross-device merge over mesh axes (inside shard_map).

    The collective twin of `merge_partials`: the same one-max/one-sum
    reduction, with pmax/psum standing in for the stacked-axis reduce.

    o: [..., dh] locally-normalized partial outputs; m/l: [...] stats.
    """
    o, _, _ = combine_partials_stats(o, m, l, axis_names)
    return o


def combine_partials_stats(o, m, l, axis_names: Sequence[str]):
    """`combine_partials` that also returns the combined (m, ℓ) stats, for
    callers that merge the cross-device result with FURTHER partials (the
    chunked-prefill path merges the sharded past-context partial with the
    in-chunk causal partial via `merge_two`)."""
    ax = tuple(axis_names)
    M = jax.lax.pmax(m, ax)
    w = l * jnp.exp(m - M)
    L = jax.lax.psum(w, ax)
    denom = jnp.maximum(L, 1e-30)
    o = jax.lax.psum(o * w[..., None], ax) / denom[..., None]
    return o, M, L


# ---------------------------------------------------------------------------
# Ring attention (train / prefill sequence parallelism)
# ---------------------------------------------------------------------------

def _attn_block_partial(q, k, v, q_pos, k_pos0, *, causal, window, is_global,
                        scale):
    """One (q-chunk × kv-chunk) partial: returns (o_normed, m, l).

    q: [B, Sq, H, dh]; k/v: [B, Sk, K, dh]; q_pos: [Sq] absolute positions;
    k_pos0: scalar absolute position of k[0].
    """
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    G = H // K
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qg = qf.reshape(B, Sq, K, G, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kf)               # [B,K,G,Sq,Sk]
    k_pos = k_pos0 + jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        in_w = k_pos[None, :] > q_pos[:, None] - window
        if is_global is not None:
            in_w = in_w | is_global
        mask &= in_w
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,K,G,Sq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, vf)
    o = o / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
    return (o.reshape(B, Sq, H, dh),
            m.transpose(0, 3, 1, 2).reshape(B, Sq, H),
            l.transpose(0, 3, 1, 2).reshape(B, Sq, H))


def ring_attention_local(q, k, v, *, axis_name: str, causal: bool,
                         window: Optional[int], is_global, scale: float):
    """Per-device body (inside shard_map): rotate KV chunks around the ring.

    q/k/v: LOCAL chunks [B, Sl, H/K, dh]; device i owns positions
    [i·Sl, (i+1)·Sl).  n_dev-1 ppermutes stream every KV chunk past every
    q chunk; online softmax merges partials.
    """
    n_dev = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Sl, H, dh = q.shape
    q_pos = idx * Sl + jnp.arange(Sl)

    def step(carry, r):
        kc, vc, o, m, l = carry
        src = (idx - r) % n_dev                                # owner of kc
        o2, m2, l2 = _attn_block_partial(
            q, kc, vc, q_pos, src * Sl, causal=causal, window=window,
            is_global=is_global, scale=scale)
        o, m, l = merge_two(o, m, l, o2, m2, l2)
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (kc, vc, o, m, l), None

    o0 = jnp.zeros((B, Sl, H, dh), jnp.float32)
    m0 = jnp.full((B, Sl, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sl, H), jnp.float32)
    (_, _, o, m, l), _ = jax.lax.scan(
        step, (k, v, o0, m0, l0), jnp.arange(n_dev))
    return o.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, *, causal: bool = True,
                   window: Optional[int] = None, is_global=None,
                   batch_axes=("data",), seq_axis: str = "model"):
    """shard_map wrapper: q/k/v [B, S, H/K, dh] seq-sharded over `seq_axis`."""
    scale = q.shape[-1] ** -0.5
    bspec = P(_axes_spec(batch_axes), seq_axis, None, None)
    fn = functools.partial(ring_attention_local, axis_name=seq_axis,
                           causal=causal, window=window, is_global=is_global,
                           scale=scale)
    if is_global is not None:
        # traced flag rides along as an argument, replicated
        fn2 = lambda qq, kk, vv, gg: functools.partial(  # noqa: E731
            ring_attention_local, axis_name=seq_axis, causal=causal,
            window=window, scale=scale)(qq, kk, vv, is_global=gg)
        return shard_map(fn2, mesh=mesh,
                         in_specs=(bspec, bspec, bspec, P()),
                         out_specs=bspec, check_vma=False)(q, k, v, is_global)
    return shard_map(fn, mesh=mesh, in_specs=(bspec, bspec, bspec),
                     out_specs=bspec, check_vma=False)(q, k, v)


# ---------------------------------------------------------------------------
# Sequence-parallel paged decode attention (the G2 dataflow proper)
# ---------------------------------------------------------------------------

def _shard_page_offset(page_axes: Sequence[str], np_local: int):
    """Linearized first-local-page index of this shard."""
    idx = 0
    for a in page_axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx * np_local


def local_append_uniform(pool_local, phys, slot, val, page_axes):
    """Append one token's K or V inside the owning shard (no cross-shard
    select): read-modify-write of a single [B, K, 1, 1, dh] slice.  phys and
    slot are uniform across the batch (lockstep decode).

    pool_local: [B, K, NP_local, T, dh]; val: [B, K, dh].
    """
    B, K, NPl, T, dh = pool_local.shape
    p_loc = phys[0] - _shard_page_offset(page_axes, NPl)
    owned = (p_loc >= 0) & (p_loc < NPl)
    p_c = jnp.clip(p_loc, 0, NPl - 1)
    zero = jnp.zeros((), jnp.int32)
    cur = jax.lax.dynamic_slice(pool_local, (zero, zero, p_c, slot[0], zero),
                                (B, K, 1, 1, dh))
    upd = jnp.where(owned, val[:, :, None, None, :].astype(pool_local.dtype),
                    cur)
    return jax.lax.dynamic_update_slice(pool_local, upd,
                                        (zero, zero, p_c, slot[0], zero))


def sharded_append_uniform(pool_k, pool_v, layer, k_new, v_new, phys, slot,
                           mesh: Mesh, *,
                           batch_axes: Sequence[str] = ("data",),
                           page_axes: Sequence[str] = ("model",),
                           k_scale=None, v_scale=None,
                           kv_quant: str = "none"):
    """In-place append of one token's K/V into FULL stacked pools
    [L, B, K, NP, T, dh] at a traced layer index, inside the owning shard
    (the paper's direct G2-die write).  Uniform lockstep positions.

    Quantized pools (kv8/kv4) carry per-page×head scales [L, B, K, NP]:
    the owning shard dequantizes ONLY the touched page, inserts the token,
    requantizes, and writes page + scale back — still O(page) per layer.
    Returns (k, v) or (k, v, k_scale, v_scale) when quantized.
    """
    from repro.core import quant

    bspec = _axes_spec(batch_axes)
    pspec = P(None, bspec, None, _axes_spec(page_axes), None, None)
    sspec = P(None, bspec, None, _axes_spec(page_axes))
    nspec = P(bspec, None, None)
    lspec = P(bspec)

    def local(kp, vp, kn, vn, ph, sl, layer):
        L, B, K, NPl, T, dh = kp.shape
        p_loc = ph[0] - _shard_page_offset(page_axes, NPl)
        owned = (p_loc >= 0) & (p_loc < NPl)
        p_c = jnp.clip(p_loc, 0, NPl - 1)
        zero = jnp.zeros((), jnp.int32)
        idx = (layer, zero, zero, p_c, sl[0], zero)

        def put(pool, val):
            cur = jax.lax.dynamic_slice(pool, idx, (1, B, K, 1, 1, dh))
            upd = jnp.where(owned,
                            val[None, :, :, None, None, :].astype(pool.dtype),
                            cur)
            return jax.lax.dynamic_update_slice(pool, upd, idx)

        return put(kp, kn), put(vp, vn)

    def local_quant(kp, vp, ks, vs, kn, vn, ph, sl, layer):
        L, B, K, NPl, Ts, dh = kp.shape
        p_loc = ph[0] - _shard_page_offset(page_axes, NPl)
        owned = (p_loc >= 0) & (p_loc < NPl)
        p_c = jnp.clip(p_loc, 0, NPl - 1)
        zero = jnp.zeros((), jnp.int32)
        pidx = (layer, zero, zero, p_c, zero, zero)
        sidx = (layer, zero, zero, p_c)

        def put(pool, scl, val):
            from repro.core.paged_kv import _zero_dead_slots
            cur_q = jax.lax.dynamic_slice(pool, pidx, (1, B, K, 1, Ts, dh))
            cur_s = jax.lax.dynamic_slice(scl, sidx, (1, B, K, 1))
            page = quant.dequantize_kv_page(cur_q[0, :, :, 0],
                                            cur_s[0, :, :, 0], kv_quant)
            page = jax.lax.dynamic_update_slice(
                page, val[:, :, None, :].astype(page.dtype),
                (zero, zero, sl[0], zero))
            page = _zero_dead_slots(page, sl[0])
            q2, s2 = quant.quantize_kv_page(page, kv_quant)
            q2 = jnp.where(owned, q2[:, :, None][None], cur_q)
            s2 = jnp.where(owned, s2[:, :, None][None], cur_s)
            return (jax.lax.dynamic_update_slice(pool, q2, pidx),
                    jax.lax.dynamic_update_slice(scl, s2, sidx))

        kp, ks = put(kp, ks, kn)
        vp, vs = put(vp, vs, vn)
        return kp, vp, ks, vs

    if kv_quant != "none":
        return shard_map(local_quant, mesh=mesh,
                         in_specs=(pspec, pspec, sspec, sspec, nspec, nspec,
                                   lspec, lspec, P()),
                         out_specs=(pspec, pspec, sspec, sspec),
                         check_vma=False)(
            pool_k, pool_v, k_scale, v_scale, k_new, v_new, phys, slot,
            jnp.asarray(layer, jnp.int32))

    return shard_map(local, mesh=mesh,
                     in_specs=(pspec, pspec, nspec, nspec, lspec, lspec,
                               P()),
                     out_specs=(pspec, pspec), check_vma=False)(
        pool_k, pool_v, k_new, v_new, phys, slot,
        jnp.asarray(layer, jnp.int32))


def sharded_prefill_fill(pool, kv_seq, layer, mesh: Mesh, *,
                         batch_axes: Sequence[str] = ("data",),
                         page_axes: Sequence[str] = ("model",),
                         scale=None, kv_quant: str = "none"):
    """Write prefill K/V [B, S, K, dh] into ONE layer of the stacked global
    pool [L, B, K, NP, T, dh], each shard packing ONLY its own page range.

    kv is replicated over the page axes already (prefill activations are
    batch-sharded), so the per-shard slice is local — a pjit-level fill
    all-gathers the ENTIRE pool per layer (measured 148 GiB × layers).

    Quantized pools (kv8/kv4): each shard quantizes its own page range and
    writes codes + per-page scales; returns (pool, scale).
    """
    from repro.core import quant

    L, Bt, K, NP, Ts, dh = pool.shape
    T = Ts * (2 if kv_quant == "kv4" else 1)
    B, S, _, _ = kv_seq.shape
    pad = NP * T - S
    kv = jnp.pad(kv_seq, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else \
        kv_seq
    bspec = _axes_spec(batch_axes)
    pspec = P(None, bspec, None, _axes_spec(page_axes), None, None)
    sspec = P(None, bspec, None, _axes_spec(page_axes))
    kvspec = P(bspec, None, None, None)

    def local(pool_l, kvv, lyr):
        _, Bl, _, NPl, _, _ = pool_l.shape
        off = _shard_page_offset(page_axes, NPl)
        zero = jnp.zeros((), jnp.int32)
        chunk = jax.lax.dynamic_slice(
            kvv, (zero, off * T, zero, zero), (Bl, NPl * T, K, dh))
        pages = chunk.reshape(Bl, NPl, T, K, dh).transpose(0, 3, 1, 2, 4)
        return jax.lax.dynamic_update_slice(
            pool_l, pages[None].astype(pool_l.dtype),
            (lyr, zero, zero, zero, zero, zero))

    def local_quant(pool_l, scale_l, kvv, lyr):
        _, Bl, _, NPl, _, _ = pool_l.shape
        off = _shard_page_offset(page_axes, NPl)
        zero = jnp.zeros((), jnp.int32)
        chunk = jax.lax.dynamic_slice(
            kvv, (zero, off * T, zero, zero), (Bl, NPl * T, K, dh))
        pages = chunk.reshape(Bl, NPl, T, K, dh).transpose(0, 3, 1, 2, 4)
        q, s = quant.quantize_kv_page(pages, kv_quant)
        pool_l = jax.lax.dynamic_update_slice(
            pool_l, q[None], (lyr, zero, zero, zero, zero, zero))
        scale_l = jax.lax.dynamic_update_slice(
            scale_l, s[None], (lyr, zero, zero, zero))
        return pool_l, scale_l

    if kv_quant != "none":
        return shard_map(local_quant, mesh=mesh,
                         in_specs=(pspec, sspec, kvspec, P()),
                         out_specs=(pspec, sspec), check_vma=False)(
            pool, scale, kv, jnp.asarray(layer, jnp.int32))

    return shard_map(local, mesh=mesh, in_specs=(pspec, kvspec, P()),
                     out_specs=pspec, check_vma=False)(
        pool, kv, jnp.asarray(layer, jnp.int32))


def sharded_window_fill(pool, kv_seq, layer, mesh: Mesh, *,
                        batch_axes: Sequence[str] = ("data",),
                        page_axes: Sequence[str] = ("model",),
                        scale=None, kv_quant: str = "none"):
    """Ring-fill the newest window pages of ONE layer, shard-locally.

    Quantized pools: shard-local page quantization; returns (pool, scale).
    """
    from repro.core import paged_kv as pk
    from repro.core import quant

    L, Bt, K, NP, Ts, dh = pool.shape
    T = Ts * (2 if kv_quant == "kv4" else 1)
    B, S, _, _ = kv_seq.shape
    n_src = pk.ceil_div(S, T)
    pad = n_src * T - S
    kv = jnp.pad(kv_seq, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else \
        kv_seq
    bspec = _axes_spec(batch_axes)
    pspec = P(None, bspec, None, _axes_spec(page_axes), None, None)
    sspec = P(None, bspec, None, _axes_spec(page_axes))
    kvspec = P(bspec, None, None, None)

    def local(pool_l, kvv, lyr, scale_l=None):
        _, Bl, _, NPl, _, _ = pool_l.shape
        off = _shard_page_offset(page_axes, NPl)
        zero = jnp.zeros((), jnp.int32)
        x = kvv.reshape(Bl, n_src, T, K, dh).transpose(0, 3, 1, 2, 4)
        if kv_quant != "none":
            x, s_all = quant.quantize_kv_page(x, kv_quant)
        for sp in range(max(0, n_src - NP), n_src):   # static, ≤ NP pages
            slot = sp % NP
            loc = slot - off
            owned = (loc >= 0) & (loc < NPl)
            loc_c = jnp.clip(loc, 0, NPl - 1)
            idx = (lyr, zero, zero, loc_c, zero, zero)
            cur = jax.lax.dynamic_slice(pool_l, idx, (1, Bl, K, 1, Ts, dh))
            upd = jnp.where(owned,
                            x[:, :, sp][None, :, :, None].astype(
                                pool_l.dtype), cur)
            pool_l = jax.lax.dynamic_update_slice(pool_l, upd, idx)
            if kv_quant != "none":
                sidx = (lyr, zero, zero, loc_c)
                cur_s = jax.lax.dynamic_slice(scale_l, sidx, (1, Bl, K, 1))
                upd_s = jnp.where(owned, s_all[:, :, sp][None, :, :, None],
                                  cur_s)
                scale_l = jax.lax.dynamic_update_slice(scale_l, upd_s, sidx)
        if kv_quant != "none":
            return pool_l, scale_l
        return pool_l

    if kv_quant != "none":
        def local_q(pool_l, scale_l, kvv, lyr):
            return local(pool_l, kvv, lyr, scale_l)
        return shard_map(local_q, mesh=mesh,
                         in_specs=(pspec, sspec, kvspec, P()),
                         out_specs=(pspec, sspec), check_vma=False)(
            pool, scale, kv, jnp.asarray(layer, jnp.int32))

    return shard_map(local, mesh=mesh, in_specs=(pspec, kvspec, P()),
                     out_specs=pspec, check_vma=False)(
        pool, kv, jnp.asarray(layer, jnp.int32))


def sharded_chunk_fill(pool, kv_chunk, layer, slot, page0, valid_len,
                       mesh: Mesh, *,
                       batch_axes: Sequence[str] = ("data",),
                       page_axes: Sequence[str] = ("model",),
                       scale=None, kv_quant: str = "none"):
    """Chunked-prefill fill of ONE slot's stripe in the sharded stacked
    global pool [L, B, K, NP, Ts, dh]: each shard writes only the
    intersection of its local page range with the chunk's pages, and only
    when it owns the slot's batch row — the direct G2-die write of the
    paper, at chunk granularity.  kv_chunk [1, C, K, dh] is replicated
    (chunk bytes are tiny against the pool).  Pages holding none of the
    `valid_len` real tokens are skipped; quantized pools (kv8/kv4) get
    whole-page codes + per-page scales.  Returns pool or (pool, scale).
    """
    from repro.core.paged_kv import _fill_chunk_pages

    L, Bt, K, NP, Ts, dh = pool.shape
    T = Ts * (2 if kv_quant == "kv4" else 1)
    bspec = _axes_spec(batch_axes)
    pspec = P(None, bspec, None, _axes_spec(page_axes), None, None)
    sspec = P(None, bspec, None, _axes_spec(page_axes))
    kvspec = P(None, None, None, None)

    def local(pool_l, kvv, lyr, sl, p0, n_valid, scale_l=None):
        # same body as the single-device fills — only the page/slot
        # coordinates shift into shard-local space, and writes outside
        # this shard's (batch row × page range) drop via valid_of
        _, Bl, _, NPl, _, _ = pool_l.shape
        b_off = _shard_page_offset(batch_axes, Bl)   # generic linear offset
        p_off = _shard_page_offset(page_axes, NPl)
        sl_loc = sl - b_off
        own_b = (sl_loc >= 0) & (sl_loc < Bl)
        return _fill_chunk_pages(
            pool_l, kvv, lyr, jnp.clip(sl_loc, 0, Bl - 1),
            lambda sp: jnp.clip(p0 + sp - p_off, 0, NPl - 1),
            lambda sp: (own_b & (p0 + sp - p_off >= 0)
                        & (p0 + sp - p_off < NPl) & (sp * T < n_valid)),
            scale=scale_l, kv_quant=kv_quant)

    args = (jnp.asarray(layer, jnp.int32), jnp.asarray(slot, jnp.int32),
            jnp.asarray(page0, jnp.int32), jnp.asarray(valid_len, jnp.int32))
    if kv_quant != "none":
        def local_q(pool_l, scale_l, kvv, lyr, sl, p0, n_valid):
            return local(pool_l, kvv, lyr, sl, p0, n_valid, scale_l)
        return shard_map(local_q, mesh=mesh,
                         in_specs=(pspec, sspec, kvspec, P(), P(), P(), P()),
                         out_specs=(pspec, sspec), check_vma=False)(
            pool, scale, kv_chunk, *args)

    return shard_map(local, mesh=mesh,
                     in_specs=(pspec, kvspec, P(), P(), P(), P()),
                     out_specs=pspec, check_vma=False)(pool, kv_chunk, *args)


def sharded_chunk_attention(q, k_pages, v_pages, page_base, start, q_pos,
                            mesh: Mesh, *,
                            window: Optional[int] = None,
                            page_axes: Sequence[str] = ("model",),
                            impl: str = "auto",
                            kv_quant: str = "none",
                            k_scale=None, v_scale=None,
                            partitions: int = 0):
    """Past-context partial attention of one slot's chunk queries against
    its page-sharded stripe (chunked prefill on a mesh).

    q: [1, S, H, dh] replicated chunk queries; pages: [1, K, NP, Ts, dh]
    the slot's stripe (batch row already sliced out), NP sharded over
    `page_axes`; page_base: [1, NP] absolute positions.  Each shard runs
    the chunk-attention oracle over its local pages and the partials merge
    via the log-sum-exp combine (the NPU softmax aggregation, at chunk
    granularity).  Returns REPLICATED combined (o, m, ℓ) so the caller can
    merge with the in-chunk causal partial.
    """
    from repro.kernels.paged_attention.ops import paged_chunk_attention

    n_page_shards = 1
    for a in page_axes:
        n_page_shards *= mesh.shape[a]

    qspec = P(None, None, None, None)
    pspec = P(None, None, _axes_spec(page_axes), None, None)
    sspec = P(None, None, _axes_spec(page_axes))
    basespec = P(None, _axes_spec(page_axes))

    def run(qq, kp, vp, base, st, qp, ks=None, vs=None):
        # `partitions` splits each shard's LOCAL page walk (resolved
        # against the local page count inside the op)
        o, m, l = paged_chunk_attention(
            qq, kp, vp, base, st, qp, window=window, impl=impl,
            kv_quant=kv_quant, k_scale=ks, v_scale=vs,
            partitions=partitions)
        if n_page_shards > 1:
            o, m, l = combine_partials_stats(o, m, l, tuple(page_axes))
        return o, m, l

    out_specs = (qspec, P(None, None, None), P(None, None, None))
    if kv_quant != "none":
        return shard_map(run, mesh=mesh,
                         in_specs=(qspec, pspec, pspec, basespec, P(), P(None),
                                   sspec, sspec),
                         out_specs=out_specs, check_vma=False)(
            q, k_pages, v_pages, page_base, jnp.asarray(start, jnp.int32),
            q_pos, k_scale, v_scale)
    return shard_map(run, mesh=mesh,
                     in_specs=(qspec, pspec, pspec, basespec, P(), P(None)),
                     out_specs=out_specs, check_vma=False)(
        q, k_pages, v_pages, page_base, jnp.asarray(start, jnp.int32), q_pos)


def paged_decode_attention_sharded(
    q, k_pages, v_pages, page_base, length, mesh: Mesh, *,
    window: Optional[int] = None, is_global=None,
    batch_axes: Sequence[str] = ("data",),
    page_axes: Sequence[str] = ("model",),
    impl: str = "auto",
    append: Optional[Tuple] = None,   # (k_new [B,K,dh], v_new, phys, slot)
    kv_quant: str = "none",
    k_scale=None, v_scale=None,       # [B, K, NP] per-page×head scales
    partitions: int = 0,              # split of each shard's local walk
):
    """q: [B, H, dh]; pages: [B, K, NP, T, dh]; page_base: [B, NP] absolute
    position of each physical page's slot 0 (<0 = unwritten);
    length: [B] context length INCLUDING the token being decoded.

    Pages sharded over `page_axes`; batch over `batch_axes`; combine via
    psum over `page_axes` (the paper's NPU aggregation step).  When `append`
    is given, the new token's K/V land in the owning shard *inside* the
    shard_map (the paper's direct G2 write) — a pjit-level update on the
    sharded page dim would lower to a full-pool ownership select per layer
    (measured: the dominant decode HLO traffic).

    Returns o, or (o, new_k_pages, new_v_pages) when appending.
    """
    from repro.kernels.paged_attention.ops import paged_attention_partial

    if append is not None and kv_quant != "none":
        raise NotImplementedError(
            "fused append+attention does not support quantized pools; "
            "the engine appends via sharded_append_uniform instead")

    n_page_shards = 1
    for a in page_axes:
        n_page_shards *= mesh.shape[a]

    bspec = _axes_spec(batch_axes)
    qspec = P(bspec, None, None)
    pspec = P(bspec, None, _axes_spec(page_axes), None, None)
    sspec = P(bspec, None, _axes_spec(page_axes))
    basespec = P(bspec, _axes_spec(page_axes))
    lenspec = P(bspec)
    nspec = P(bspec, None, None)

    def run(qq, kp, vp, base, ln, ks=None, vs=None):
        o, m, l = paged_attention_partial(qq, kp, vp, base, ln,
                                          window=window, is_global=is_global,
                                          impl=impl, kv_quant=kv_quant,
                                          k_scale=ks, v_scale=vs,
                                          partitions=partitions)
        if n_page_shards > 1:
            o = combine_partials(o, m, l, tuple(page_axes))
        return o.astype(qq.dtype)

    if append is None and kv_quant != "none":
        return shard_map(run, mesh=mesh,
                         in_specs=(qspec, pspec, pspec, basespec, lenspec,
                                   sspec, sspec),
                         out_specs=qspec, check_vma=False)(
            q, k_pages, v_pages, page_base, length, k_scale, v_scale)

    if append is None:
        return shard_map(run, mesh=mesh,
                         in_specs=(qspec, pspec, pspec, basespec, lenspec),
                         out_specs=qspec, check_vma=False)(
            q, k_pages, v_pages, page_base, length)

    def run_append(qq, kp, vp, base, ln, kn, vn, phys, slot):
        kp = local_append_uniform(kp, phys, slot, kn, page_axes)
        vp = local_append_uniform(vp, phys, slot, vn, page_axes)
        return run(qq, kp, vp, base, ln), kp, vp

    k_new, v_new, phys, slot = append
    return shard_map(run_append, mesh=mesh,
                     in_specs=(qspec, pspec, pspec, basespec, lenspec,
                               nspec, nspec, lenspec, lenspec),
                     out_specs=(qspec, pspec, pspec), check_vma=False)(
        q, k_pages, v_pages, page_base, length, k_new, v_new, phys, slot)


# ---------------------------------------------------------------------------
# Shared-pool (FTL-mapped) variants: P_total sharded over the mesh
# ---------------------------------------------------------------------------
#
# The shared pool [K, P_total, T, dh] shards its PHYSICAL page axis over
# `page_axes` (the paper's G2 dies); page tables hold GLOBAL physical
# indices, so each shard subtracts its page offset and masks entries
# outside its local range — a table walk is shard-local arithmetic, and
# the KV bytes still never cross the interconnect.

def paged_decode_attention_sharded_shared(
    q, k_pages, v_pages, page_table, page_base, length, mesh: Mesh, *,
    window: Optional[int] = None, is_global=None,
    batch_axes: Sequence[str] = ("data",),
    page_axes: Sequence[str] = ("model",),
    impl: str = "auto",
    kv_quant: str = "none",
    k_scale=None, v_scale=None,       # [K, P_total] per-page×head scales
    partitions: int = 0,              # split of each shard's local walk
):
    """q: [B, H, dh]; pages: [K, P_total, T, dh] sharded on P_total;
    page_table: [B, NP] GLOBAL physical indices; page_base: [B, NP] base
    position of LOGICAL page j (<0 = unwritten); length: [B].

    Each shard translates the table into its local page range (entries it
    does not own become data-invalid via page_base = -1e9), runs the
    shared-pool partial over its local pages, and the partials merge via
    the log-sum-exp combine (the paper's NPU aggregation).
    """
    from repro.kernels.paged_attention.ops import paged_attention_partial

    n_page_shards = 1
    for a in page_axes:
        n_page_shards *= mesh.shape[a]

    bspec = _axes_spec(batch_axes)
    qspec = P(bspec, None, None)
    pspec = P(None, _axes_spec(page_axes), None, None)
    sspec = P(None, _axes_spec(page_axes))
    tspec = P(bspec, None)
    lenspec = P(bspec)

    def run(qq, kp, vp, tbl, base, ln, ks=None, vs=None):
        P_local = kp.shape[1]
        off = _shard_page_offset(page_axes, P_local)
        tl = tbl - off
        owned = (tl >= 0) & (tl < P_local)
        base_l = jnp.where(owned, base, -(10 ** 9))
        tl = jnp.clip(tl, 0, P_local - 1)
        o, m, l = paged_attention_partial(
            qq, kp, vp, base_l, ln, window=window, is_global=is_global,
            impl=impl, kv_quant=kv_quant, k_scale=ks, v_scale=vs,
            page_table=tl, partitions=partitions)
        if n_page_shards > 1:
            o = combine_partials(o, m, l, tuple(page_axes))
        return o.astype(qq.dtype)

    if kv_quant != "none":
        return shard_map(run, mesh=mesh,
                         in_specs=(qspec, pspec, pspec, tspec, tspec,
                                   lenspec, sspec, sspec),
                         out_specs=qspec, check_vma=False)(
            q, k_pages, v_pages, page_table, page_base, length,
            k_scale, v_scale)
    return shard_map(run, mesh=mesh,
                     in_specs=(qspec, pspec, pspec, tspec, tspec, lenspec),
                     out_specs=qspec, check_vma=False)(
        q, k_pages, v_pages, page_table, page_base, length)


def sharded_append_shared(pool_k, pool_v, layer, k_new, v_new, phys, slot,
                          mesh: Mesh, *,
                          batch_axes: Sequence[str] = ("data",),
                          page_axes: Sequence[str] = ("model",),
                          k_scale=None, v_scale=None,
                          kv_quant: str = "none"):
    """One-token append into FULL stacked shared pools [L, K, P, T, dh]
    at a traced layer index: the shard owning each sequence's physical
    page scatters locally; everyone else's write drops (ragged positions,
    so this is the continuous-batching path on a mesh).

    Returns (k, v) or (k, v, k_scale, v_scale) when quantized.

    NB: the shared pool has no batch dim, so over any BATCH mesh axes the
    pool is replicated — every replica must apply the SAME full-batch
    append or the copies diverge.  The new-token values/positions are
    therefore replicated into the shard_map (a [B, K, dh] vector against
    a pool measured in GB), and only the PAGE axes select which shard's
    local range actually lands the write.
    """
    from repro.core import paged_kv as pk

    del batch_axes                       # see NB above — values replicate
    pspec = P(None, None, _axes_spec(page_axes), None, None)
    sspec = P(None, None, _axes_spec(page_axes))
    nspec = P(None, None, None)
    lspec = P(None)

    def local(kp, vp, kn, vn, ph, sl, lyr):
        P_local = kp.shape[2]
        off = _shard_page_offset(page_axes, P_local)
        ph_loc = ph - off
        ph_drop = jnp.where((ph_loc >= 0) & (ph_loc < P_local), ph_loc,
                            P_local)
        kp = pk.append_global_shared(kp, lyr, ph_drop, sl, kn)
        vp = pk.append_global_shared(vp, lyr, ph_drop, sl, vn)
        return kp, vp

    def local_quant(kp, vp, ks, vs, kn, vn, ph, sl, lyr):
        P_local = kp.shape[2]
        off = _shard_page_offset(page_axes, P_local)
        ph_loc = ph - off
        ph_drop = jnp.where((ph_loc >= 0) & (ph_loc < P_local), ph_loc,
                            P_local)
        kp, ks = pk.append_token_quant_shared(kp, ks, lyr, ph_drop, sl, kn,
                                              kv_quant)
        vp, vs = pk.append_token_quant_shared(vp, vs, lyr, ph_drop, sl, vn,
                                              kv_quant)
        return kp, vp, ks, vs

    lyr = jnp.asarray(layer, jnp.int32)
    if kv_quant != "none":
        return shard_map(local_quant, mesh=mesh,
                         in_specs=(pspec, pspec, sspec, sspec, nspec, nspec,
                                   lspec, lspec, P()),
                         out_specs=(pspec, pspec, sspec, sspec),
                         check_vma=False)(
            pool_k, pool_v, k_scale, v_scale, k_new, v_new, phys, slot, lyr)
    return shard_map(local, mesh=mesh,
                     in_specs=(pspec, pspec, nspec, nspec, lspec, lspec,
                               P()),
                     out_specs=(pspec, pspec), check_vma=False)(
        pool_k, pool_v, k_new, v_new, phys, slot, lyr)


def _axes_spec(axes: Sequence[str]):
    axes = tuple(axes)
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes
