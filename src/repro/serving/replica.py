"""Disaggregated prefill/decode: KV page migration between replicas.

This module is the data plane of the multi-replica story (DESIGN.md
§16).  A request that chunk-prefilled on one `ContinuousBatcher` can
move to another — typically a dedicated PREFILL replica handing off to a
DECODE replica — by serializing everything the decode side needs into a
`KVEnvelope`:

  * the request's page-table slice as PAGE BYTES in logical order (the
    physical ids are replica-local and never travel): one `[L, K, T, dh]`
    block per mapped global-pool page, per pool leaf — quantized kv8/kv4
    codes and their per-page scales ride as leaves like any other;
  * window-ring pages (local-attention archs) plus the slot's
    `page_pos_w` ring-base row;
  * recurrent state rows (rwkv / ssm / hybrid families);
  * the scalar `lengths` entry, the emitted output so far (the prefill
    handoff token), per-token logprobs, and the request's
    `SamplingParams` with its RESOLVED PRNG seed — `_seed_of` folds the
    batcher seed and uid, so the envelope pins the stream explicitly and
    the decode replica continues `fold_in(seed, position)` exactly where
    prefill stopped.  Token identity across the migration is therefore a
    consequence of PR 4's stream design, not a new mechanism.

The leaves are flattened with the checkpoint machinery
(`checkpoint._flatten_with_paths`) into a flat ``{path: np.ndarray}``
dict; `to_bytes`/`from_bytes` give the wire form (npz payload + JSON
header) the router actually ships, so migration cost is measurable in
real bytes.

Import allocates FRESH physical pages on the destination (admission
accounting mirrors `_admit_shared`: worst-case footprint against free
pages, hot-tier reservations under DESIGN.md §13 tiering) and splices
the bytes through the `paged_kv` writers only — `stage_hot_slot` for
page bytes (the flat-pool physical index plays the hot-slot role),
`import_slot_rows` for per-slot rows — keeping kvlint's KV004 invariant
intact: no pool-leaf write outside `core/paged_kv.py`.

`PrefixPageIndex` is the cross-replica prefix-cache index: full-page KV
bytes keyed by their token chain, published from any replica's local
`PrefixCache` and importable into another's pool so system-prompt pages
warmed on replica A admit as prefix hits on replica B.
"""
from __future__ import annotations

import dataclasses
import io
import json
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.checkpoint.checkpoint import _flatten_with_paths
from repro.core import paged_kv
from repro.core.page_alloc import OutOfPages
from repro.serving.sampler import SamplingParams
from repro.serving.scheduler import (ContinuousBatcher, Request,
                                     _watch_jit)

ENVELOPE_VERSION = 1

# per-slot rows with a [L, B, ...] layout (batch axis 1) that migrate as
# [L, ...] stacks; page-table / ring-base / lengths rows are batch-axis 0
_STATE_ROW_LEAVES = ("rwkv_state", "rwkv_shift", "rwkv_shift2",
                     "ssm_state", "conv_tail")
_WINDOW_LEAVES = ("k_pages_w", "v_pages_w", "k_scale_w", "v_scale_w")


def _window_leaves(cache) -> List[str]:
    return [n for n in _WINDOW_LEAVES if getattr(cache, n) is not None]


def _page_bytes(batcher: ContinuousBatcher, phys: int,
                leaves: Sequence[str]) -> Dict[str, np.ndarray]:
    """One physical page's bytes per pool leaf, wherever they live: the
    device pool (flat), the hot tier (tiered resident — mapped pages are
    pinned hot, so a live slot's pages always read here), or the host
    capacity store (tiered demoted — prefix-cache pages between uses)."""
    if batcher.tier is not None:
        if batcher.tier.is_resident(phys):
            s = batcher.tier.slot_of(phys)
            return {n: np.asarray(getattr(batcher.cache, n)[:, :, s])
                    for n in leaves}
        return {n: np.array(v) for n, v in batcher._store[phys].items()}
    return {n: np.asarray(getattr(batcher.cache, n)[:, :, phys])
            for n in leaves}


@dataclasses.dataclass
class KVEnvelope:
    """One migratable request: a JSON-able header plus the flat
    ``{path: array}`` leaf dict produced by the checkpoint flattener.

    Array paths: ``prompt`` / ``output`` / ``logprobs``,
    ``pages_g/<j>/<leaf>`` and ``pages_w/<j>/<leaf>`` per logical page j,
    ``page_pos_w``, and ``state/<leaf>`` rows."""
    meta: Dict[str, Any]
    arrays: Dict[str, np.ndarray]

    @property
    def uid(self) -> int:
        return int(self.meta["uid"])

    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.arrays.values()))

    def to_bytes(self) -> bytes:
        """Wire form: 8-byte header length, JSON header, npz payload."""
        buf = io.BytesIO()
        np.savez(buf, **self.arrays)
        header = json.dumps(self.meta, sort_keys=True).encode()
        return (len(header).to_bytes(8, "little") + header
                + buf.getvalue())

    @classmethod
    def from_bytes(cls, data: bytes) -> "KVEnvelope":
        hlen = int.from_bytes(data[:8], "little")
        meta = json.loads(data[8:8 + hlen].decode())
        if meta.get("version") != ENVELOPE_VERSION:
            raise ValueError(
                f"KVEnvelope version {meta.get('version')} != "
                f"{ENVELOPE_VERSION}")
        npz = np.load(io.BytesIO(data[8 + hlen:]))
        return cls(meta=meta, arrays={k: npz[k] for k in npz.files})


def _slot_of(batcher: ContinuousBatcher, uid: int) -> int:
    for i, r in enumerate(batcher.slots):
        if r is not None and r.uid == uid:
            return i
    raise KeyError(f"uid {uid} occupies no slot (queued, finished, or "
                   "unknown)")


def export_request(batcher: ContinuousBatcher, uid: int) -> KVEnvelope:
    """Serialize one slot-resident request's KV state.  Read-only: the
    source keeps its pages until `finish_migrated` — the router releases
    only after the destination import succeeded, so a failed import
    retries without losing the request."""
    if not batcher.shared:
        raise ValueError(
            "KV migration needs the shared-pool layout (physical pages "
            "addressed through tables); stripe caches have no per-page "
            "identity to serialize — run replicas with "
            "EngineConfig(shared_pool=True)")
    i = _slot_of(batcher, uid)
    req = batcher.slots[i]
    if i in batcher._prefill_live:
        raise ValueError(f"uid {uid} is mid-chunked-prefill; export "
                         "after the prefill handoff token")
    if not req.output:
        raise ValueError(f"uid {uid} has no emitted token yet")
    c = batcher.cache
    T = batcher.engine.eng.page_tokens
    length = int(batcher._lengths[i])

    tree: Dict[str, Any] = {
        "prompt": np.asarray(req.prompt, np.int32),
        "output": np.asarray(req.output, np.int32),
        "logprobs": np.asarray(req.logprobs, np.float64),
    }
    n_pg = 0
    if batcher.alloc is not None:
        pages = batcher._slot_pages[i]
        n_pg = len(pages)
        assert sorted(pages) == list(range(n_pg)), \
            f"non-contiguous logical pages {sorted(pages)}"
        assert n_pg == -(-length // T), (n_pg, length, T)
        tree["pages_g"] = {
            f"{j:04d}": _page_bytes(batcher, pages[j],
                                    batcher._pool_leaves)
            for j in range(n_pg)}
    n_pw = 0
    if batcher.alloc_w is not None:
        ring = batcher._slot_ring[i]
        n_pw = len(ring)
        wl = _window_leaves(c)
        tree["pages_w"] = {
            f"{j:04d}": {n: np.asarray(getattr(c, n)[:, :, p])
                         for n in wl}
            for j, p in enumerate(ring)}
    tree["page_pos_w"] = (np.asarray(c.page_pos_w[i])
                          if c.page_pos_w is not None else None)
    state = {n: np.asarray(getattr(c, n)[:, i])
             for n in _STATE_ROW_LEAVES if getattr(c, n) is not None}
    tree["state"] = state or None

    p = req.params
    meta = {
        "version": ENVELOPE_VERSION,
        "uid": req.uid,
        "length": length,
        "n_pages_g": n_pg,
        "n_pages_w": n_pw,
        "page_tokens": T,
        "kv_quant": batcher.engine.eng.kv_quant,
        "seed": int(batcher._seed_of(req)),
        "priority": req.priority,
        "deadline_ts": req.deadline_ts,
        "submit_ts": req.submit_ts,
        "first_ts": req.first_ts,
        "params": {
            "temperature": p.temperature, "top_k": p.top_k,
            "top_p": p.top_p, "max_new_tokens": p.max_new_tokens,
            "stop_token_ids": list(p.stop_token_ids),
            "logprobs": p.logprobs, "speculation": p.speculation,
        },
    }
    return KVEnvelope(meta=meta, arrays=_flatten_with_paths(tree))


def finish_migrated(batcher: ContinuousBatcher, uid: int) -> None:
    """Release the source half of a completed migration: the slot
    retires with ``finish_reason="migrated"`` and its pages go back
    through the allocator (prefix-cache references survive, exactly as
    on any other finish)."""
    i = _slot_of(batcher, uid)
    batcher._prefill_live.pop(i, None)
    batcher._finish(i, "migrated")
    batcher.stats["migrations_out"] = (
        batcher.stats.get("migrations_out", 0) + 1)


def _migrate_jits(batcher: ContinuousBatcher):
    """Lazily attach (and JIT_WATCH-register) the import writers: page
    staging reuses the batcher's `_stage_jit` (global leaves — the same
    compiled signature the tiered promoter uses); window pages and the
    per-slot rows get their own one-signature callables."""
    if getattr(batcher, "_migrate_rows_jit", None) is None:
        # per-batcher closures (not the bare module function): jax keys
        # the compile cache by function identity, so batchers of
        # different shapes would otherwise share — and grow — one cache
        def _rows(cache, i, rows):
            return paged_kv.import_slot_rows(cache, i, rows)

        batcher._migrate_rows_jit = jax.jit(_rows, donate_argnums=(0,))
        _watch_jit(f"{type(batcher).__name__}._migrate_rows",
                   batcher._migrate_rows_jit)
    if (batcher.alloc_w is not None
            and getattr(batcher, "_stage_w_jit", None) is None):
        def _stage_w(cache, slot, vals):
            return paged_kv.stage_hot_slot(cache, slot, vals)

        batcher._stage_w_jit = jax.jit(_stage_w, donate_argnums=(0,))
        _watch_jit(f"{type(batcher).__name__}._migrate_stage_w",
                   batcher._stage_w_jit)
    return batcher._migrate_rows_jit


def _stage_page(batcher: ContinuousBatcher, dst: int,
                vals: Dict[str, np.ndarray], *, window: bool) -> None:
    fn = batcher._stage_w_jit if window else batcher._stage_jit
    batcher._count_compile("migrate_stage_w" if window
                           else "tier_stage")
    batcher.cache = fn(batcher.cache, *batcher._put((np.int32(dst), vals)))


def import_request(batcher: ContinuousBatcher,
                   env: KVEnvelope) -> Optional[Request]:
    """Splice a migrated request into a free slot of `batcher`.

    Returns the (fresh) Request now decoding here, or None when the
    destination cannot take it YET — no free slot, or the worst-case
    footprint does not fit the pool / hot tier net of reservations (the
    same bound `_admit_shared` enforces, so an admitted import can never
    run out of pages or hot slots mid-decode).  Config mismatches raise:
    migration is only defined between replicas serving the same model
    and cache layout."""
    if not batcher.shared:
        raise ValueError("KV migration import needs a shared-pool "
                         "batcher (EngineConfig.shared_pool=True)")
    m = env.meta
    T = batcher.engine.eng.page_tokens
    if m["page_tokens"] != T or m["kv_quant"] != batcher.engine.eng.kv_quant:
        raise ValueError(
            f"KVEnvelope layout (page_tokens={m['page_tokens']}, "
            f"kv_quant={m['kv_quant']!r}) does not match this replica "
            f"(page_tokens={T}, "
            f"kv_quant={batcher.engine.eng.kv_quant!r})")
    free = [i for i, r in enumerate(batcher.slots)
            if r is None and i not in batcher._prefill_live]
    if not free:
        return None
    i = free[0]

    params = SamplingParams(seed=int(m["seed"]), **m["params"])
    req = Request(
        uid=int(m["uid"]), prompt=[int(t) for t in env.arrays["prompt"]],
        max_new=params.max_new_tokens, params=params,
        output=[int(t) for t in env.arrays["output"]],
        logprobs=[float(v) for v in env.arrays["logprobs"]],
        priority=int(m["priority"]), deadline_ts=m["deadline_ts"],
        submit_ts=m["submit_ts"], first_ts=m["first_ts"])
    length = int(m["length"])
    n_pg, n_pw = int(m["n_pages_g"]), int(m["n_pages_w"])

    # -- admission accounting (mirror of _admit_shared): every imported
    # page is a FRESH allocation here, so the whole worst-case footprint
    # must fit free + cache-evictable pages net of reservations
    need_g = batcher._pages_needed(req) if batcher.alloc is not None else 0
    if batcher.alloc is not None:
        assert n_pg == -(-length // T), (n_pg, length, T)
        evictable = (batcher.prefix_cache.evictable_pages()
                     if batcher.prefix_cache is not None else 0)
        avail = (batcher.alloc.free_count + evictable
                 - batcher._outstanding)
        if need_g > avail:
            return None
        if batcher.tier is not None \
                and batcher._hot_out + need_g > batcher.tier.hot_slots:
            return None
    if batcher.alloc_w is not None and n_pw > batcher.alloc_w.free_count:
        return None

    _migrate_jits(batcher)
    # -- page bytes: allocate destination-local physical pages and stage
    # each logical page's leaves through the one staging writer
    if batcher.alloc is not None:
        for j in range(n_pg):
            p = batcher._alloc_g(j)
            batcher._slot_pages[i][j] = p
            if batcher.tier is not None:
                batcher._table_np[i, j] = batcher._bind_slot(p)
                batcher.tier.pin(p)
                dst = int(batcher._table_np[i, j])
            else:
                batcher._table_np[i, j] = p
                dst = p
            vals = {n: env.arrays[f"pages_g/{j:04d}/{n}"]
                    for n in batcher._pool_leaves}
            _stage_page(batcher, dst, vals, window=False)
    if batcher.alloc_w is not None:
        wl = _window_leaves(batcher.cache)
        for j in range(n_pw):
            p = batcher.alloc_w.alloc_for_logical(j)
            batcher._slot_ring[i].append(p)
            batcher._table_w_np[i, j] = p
            vals = {n: env.arrays[f"pages_w/{j:04d}/{n}"] for n in wl}
            _stage_page(batcher, p, vals, window=True)

    # -- per-slot rows: lengths, ring bases, recurrent state
    rows: Dict[str, np.ndarray] = {"lengths": np.asarray(length)}
    if batcher.cache.page_pos_w is not None:
        rows["page_pos_w"] = env.arrays["page_pos_w"]
    for n in _STATE_ROW_LEAVES:
        if getattr(batcher.cache, n) is not None:
            rows[n] = env.arrays[f"state/{n}"]
    batcher._count_compile("migrate_rows")
    batcher.cache = batcher._migrate_rows_jit(
        batcher.cache, *batcher._put((np.int32(i), rows)))

    # -- host bookkeeping: the slot now looks exactly like one whose
    # chunked prefill just handed off
    req.order = batcher._submit_seq
    batcher._submit_seq += 1
    batcher.slots[i] = req
    batcher._set_slot_params(i, req)
    batcher._lengths[i] = length
    batcher._resv[i] = need_g - n_pg
    batcher._outstanding += need_g - n_pg
    if batcher.tier is not None:
        batcher._hot_resv[i] = need_g
        batcher._hot_out += need_g
    batcher._tables_dirty = True
    batcher._push_tables()
    batcher.stats["migrations_in"] = (
        batcher.stats.get("migrations_in", 0) + 1)
    batcher.stats["admits"] += 1
    return req


def build_replica(config=None, *, cfg=None, params=None, rt=None,
                  device=None):
    """Construct a `KVNANDServer` whose weights and KV cache live on
    `device` (replica placement for multi-device fleets — e.g. CI's
    ``--xla_force_host_platform_device_count=4`` harness, or one model
    per accelerator).  Migration and the prefix index move bytes
    through the host, so envelopes cross device boundaries without any
    collective; `device=None` builds on the default device."""
    from repro.serving.api import KVNANDServer
    return KVNANDServer(config, cfg=cfg, params=params, rt=rt,
                        device=device)


class PrefixPageIndex:
    """Cross-replica prefix-cache index (DESIGN.md §16).

    Maps full-page token chains — the same radix keys `PrefixCache`
    uses — to host-side page BYTES per pool leaf.  `publish_from` reads
    a replica's local cache chain for a prompt and records pages the
    index lacks; `warm` imports the chain's missing tail into another
    replica's pool and registers it in that replica's local cache, so
    the next admission of the prompt maps warm pages (a prefix hit)
    instead of re-prefilling.  Tiered destinations land imported bytes
    in the CAPACITY store: the map-in path (or the queue-ahead
    prefetcher) promotes them exactly like any other demoted page.

    Bounded LRU over pages; eviction only drops index bytes, never a
    replica's own cache entries."""

    def __init__(self, page_tokens: int, max_pages: int = 512):
        self.T = page_tokens
        self.max_pages = max_pages
        self._pages: "OrderedDict[Tuple[int, ...], Dict[str, np.ndarray]]" \
            = OrderedDict()
        self.published_pages = 0
        self.warmed_pages = 0

    def __len__(self) -> int:
        return len(self._pages)

    def publish_from(self, batcher: ContinuousBatcher,
                     prompt: Sequence[int]) -> int:
        """Record the full-page chain the replica's local cache holds for
        `prompt`; returns pages newly added to the index."""
        if batcher.prefix_cache is None or batcher.alloc is None:
            return 0
        hit = batcher.prefix_cache.lookup(prompt, record=False)
        n_full = len(prompt) // self.T
        pages = (hit.exact.pages[:n_full] if hit.exact is not None
                 else hit.full_pages)
        toks = tuple(int(t) for t in prompt)
        added = 0
        for j, p in enumerate(pages):
            key = toks[:(j + 1) * self.T]
            if key in self._pages:
                self._pages.move_to_end(key)
                continue
            self._pages[key] = _page_bytes(batcher, int(p),
                                           batcher._pool_leaves)
            added += 1
        while len(self._pages) > self.max_pages:
            self._pages.popitem(last=False)
        self.published_pages += added
        return added

    def chain(self, prompt: Sequence[int]) -> List[Dict[str, np.ndarray]]:
        """The deepest contiguous full-page chain the index holds for
        `prompt` (strict h·T < len, matching `PrefixCache.lookup`)."""
        toks = tuple(int(t) for t in prompt)
        out: List[Dict[str, np.ndarray]] = []
        while (len(out) + 1) * self.T < len(toks):
            key = toks[:(len(out) + 1) * self.T]
            vals = self._pages.get(key)
            if vals is None:
                break
            self._pages.move_to_end(key)
            out.append(vals)
        return out

    def warm(self, batcher: ContinuousBatcher,
             prompt: Sequence[int]) -> int:
        """Import into `batcher` the chain pages its local cache lacks:
        allocate a page, stage the bytes (flat pool) or park them in the
        capacity store (tiered), register the extended chain, and drop
        the import reference so the local cache is the sole owner.
        Returns pages imported; backs off silently under page pressure
        (warming is an optimization, never an obligation)."""
        if batcher.prefix_cache is None or batcher.alloc is None:
            return 0
        local = batcher.prefix_cache.lookup(prompt, record=False)
        if local.exact is not None:
            return 0
        have = len(local.full_pages)
        chain = self.chain(prompt)
        if len(chain) <= have:
            return 0
        _migrate_jits(batcher)
        new_pages: List[int] = []
        for j in range(have, len(chain)):
            if batcher.alloc.free_count - batcher._outstanding <= 0:
                break
            try:
                p = batcher._alloc_g(j)
            except (OutOfPages, RuntimeError):
                break
            if batcher.tier is not None:
                batcher._store[p] = {n: np.array(v)
                                     for n, v in chain[j].items()}
            else:
                _stage_page(batcher, p, chain[j], window=False)
            new_pages.append(p)
        if not new_pages:
            return 0
        n_reg = have + len(new_pages)
        pages = [int(p) for p in local.full_pages] + new_pages
        batcher.prefix_cache.register(list(prompt)[:n_reg * self.T],
                                      pages, None, include_exact=False)
        batcher.alloc.free(new_pages)     # the cache reference remains
        self.warmed_pages += len(new_pages)
        return len(new_pages)
