"""100K-context decode through the shared-pool kv8 path.

The scenario the split-page walk exists for: a single sequence whose KV
pool (1568 pages × 64 tokens ≈ 100K context) would blow the memory /
cache budget as one monolithic score tensor.  Prefilling 100K tokens for
real is out of tier-1 budget, so the cache state is fabricated — an
identity page table over a fully-allocated shared pool of random kv8
codes — which exercises exactly the same decode path (table walk,
dequant, partitioned attention, append) as a real prefill would.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import EngineConfig, get_config
from repro.core.engine import KVNANDEngine
from repro.kernels.paged_attention import resolve_partitions
from repro.models.registry import Model
from repro.models.transformer import Runtime

CTX = 100_352          # 1568 pages of 64 tokens; 16 | 1568
PAGE_T = 64
LENGTH = 100_000


def _fabricate_cache(eng_api, cfg, seed=0):
    """Fill an init_cache skeleton as if ~100K tokens were resident."""
    cache = eng_api.init_cache(1, CTX)
    rng = np.random.default_rng(seed)
    NP = cache.page_table_g.shape[1]
    repl = {
        "k_pages_g": rng.integers(-127, 128, cache.k_pages_g.shape,
                                  dtype=np.int8),
        "v_pages_g": rng.integers(-127, 128, cache.v_pages_g.shape,
                                  dtype=np.int8),
        "k_scale_g": rng.uniform(0.005, 0.02, cache.k_scale_g.shape),
        "v_scale_g": rng.uniform(0.005, 0.02, cache.v_scale_g.shape),
        # identity logical->physical mapping over the whole pool
        "page_table_g": np.arange(NP, dtype=np.int32)[None],
        "lengths": np.array([LENGTH], np.int32),
    }
    for name, val in repl.items():
        leaf = getattr(cache, name)
        object.__setattr__(cache, name,
                           jnp.asarray(val, dtype=leaf.dtype))
    return cache


def test_100k_decode_shared_kv8():
    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    params = Model(cfg, rt).init(jax.random.PRNGKey(0))
    eng = EngineConfig(shared_pool=True, kv_quant="kv8",
                       page_tokens=PAGE_T)
    api = KVNANDEngine(cfg, eng, rt)

    # the auto ladder actually splits at this page count
    assert resolve_partitions(eng.attn_partitions,
                              CTX // PAGE_T) > 1

    cache = _fabricate_cache(api, cfg)
    tok = jnp.array([[7]], jnp.int32)
    for step in range(3):
        logits, cache = api.decode_step(params, cache, tok)
        assert logits.shape == (1, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits))), f"step {step}"
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    assert int(cache.lengths[0]) == LENGTH + 3


def test_100k_decode_partition_count_invariant():
    """The split is a pure reassociation: explicit partitions=1 and the
    auto 16-way split produce the same logits at 100K context."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    params = Model(cfg, rt).init(jax.random.PRNGKey(0))
    logits = []
    for parts in (1, 0):           # monolithic vs auto (16 at 1568 pages)
        eng = EngineConfig(shared_pool=True, kv_quant="kv8",
                           page_tokens=PAGE_T, attn_partitions=parts)
        api = KVNANDEngine(cfg, eng, rt)
        cache = _fabricate_cache(api, cfg)
        lg, _ = api.decode_step(params, cache, jnp.array([[7]], jnp.int32))
        logits.append(np.asarray(lg, np.float32))
    np.testing.assert_allclose(logits[0], logits[1], atol=2e-3, rtol=2e-3)
