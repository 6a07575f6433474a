"""DSE framework: heatmaps, OOM blanks, paper takeaways, engine coupling."""
from repro.configs import get_config
from repro.core import dse


def test_heatmap_shape_and_oom_blanks():
    cfg = get_config("opt-30b")
    grid = dse.heatmap(cfg, [1_000, 50_000, 100_000], total_dies=8,
                       wbits=8, abits=8)
    assert len(grid) == 8                       # 7 D-splits + C
    # MHA at 100K with W8A8 KV overflows small G2 allocations -> blanks
    import math
    blanks = [name for name, row in grid.items()
              if math.isinf(row[100_000])]
    assert blanks, "expected OOM blanks for G2-starved configs"


def test_weights_must_fit_g1():
    """Large models are incompatible with too-few G1 dies (Fig 15 text)."""
    cfg = get_config("llama3.1-70b")
    p = [x for x in dse.sweep(cfg, [1_000], 8, 8, 8)
         if x.system.startswith("KVNAND-D-(1+")]
    assert all(x.oom for x in p)                # 70B W8 > 1 die capacity


def test_takeaways():
    t = dse.takeaways(get_config("opt-30b"), get_config("llama3.1-70b"))
    assert all(t.values()), t


def test_recommend_engine_config():
    eng_long = dse.recommend_engine_config("llama3.1-70b", 100_000)
    eng_short = dse.recommend_engine_config("llama3.1-70b", 128)
    assert eng_long.quant in ("w4a16", "w8a8")
    point = dse.recommend_point("llama3.1-70b", 128)
    assert point.system.startswith(("KVNAND-C", "KVNAND-D"))
    assert eng_short.quant == ("w4a16" if point.wbits == 4 else "w8a8")


def test_recommend_attn_partitions_by_context():
    """Split-page attention is a long-context knob: the softmax stream
    only has something to hide under when the KV walk dominates, so the
    DSE keeps partitions = 1 at short context and splits at 100K."""
    eng_long = dse.recommend_engine_config("llama3.1-70b", 100_000)
    eng_short = dse.recommend_engine_config("llama3.1-70b", 1_000)
    assert eng_long.attn_partitions > 1
    assert eng_short.attn_partitions == 1
    # the recommended count comes from the swept ladder
    assert eng_long.attn_partitions in dse.ATTN_PARTITIONS


def test_attn_partitions_latency_monotone_gain():
    """partitions > 1 never makes the model slower at long context and
    the gain itself grows with context (more walk to hide under)."""
    from repro.core import flashsim as fs
    cfg = get_config("llama3.1-70b")
    sys = fs.kvnand_d(8, 8, 4, 16, kv_bits=8)
    gains = []
    for seq in (16_000, 50_000, 100_000):
        base = fs.decode_token_latency(sys, cfg, seq).total
        split = fs.decode_token_latency(sys, cfg, seq, partitions=16).total
        gains.append(base / split)
    assert all(g >= 1.0 for g in gains)
    assert gains == sorted(gains)


def test_best_config_prefers_bigger_g2_at_longer_ctx():
    cfg = get_config("llama3.1-70b")
    b_short = dse.best_discrete(cfg, 1_000, 8, 4, 16)
    b_long = dse.best_discrete(cfg, 100_000, 8, 4, 16)
    assert b_long.g2 > b_short.g2               # paper: 4 dies in G2 @100K


def test_recommend_hot_pages():
    """Tiered hot-tier sizing (DESIGN.md §13): SRAM-derived floor,
    pinned-working-set floor, and the degenerate single-tier case."""
    import pytest
    from repro.core import flashsim as fs
    cfg = get_config("llama3.1-8b")
    sys = fs.kvnand_d(8, 8, 4, 16, kv_bits=8)
    base = fs.hot_tier_pages(sys, cfg, 64)
    # short context: max(SRAM pages, working set of one 128-tok slot)
    assert dse.recommend_hot_pages(sys, cfg, 128) == max(base, 2)
    # long context, many slots: the pinned working set dominates (a
    # mapped hot page is never demoted, so admission needs the room)
    hp = dse.recommend_hot_pages(sys, cfg, 100_000, slots=4)
    assert hp == 4 * -(-100_000 // 64)
    assert hp > base
    # hot tier >= whole flash pool: tiering buys nothing -> 0
    assert dse.recommend_hot_pages(sys, cfg, 128,
                                   total_pages=max(base, 2)) == 0
    with pytest.raises(ValueError):
        dse.recommend_hot_pages(sys, cfg, 128, slots=0)


def test_recommend_overlap():
    """Pipelined stepping is a host-overhead knob (DESIGN.md §14): the
    DSE only recommends it when measured host time is worth hiding."""
    from repro.core import flashsim as fs
    cfg = get_config("llama3.1-8b")
    sys = fs.kvnand_d(8, 8, 4, 16, kv_bits=8)
    dev = fs.serving_step_time(sys, cfg, 10_000, 0.0, overlap=False)
    # host work comparable to device time: overlap wins
    assert dse.recommend_overlap(sys, cfg, 10_000, dev)
    # negligible host work: speedup < min_speedup, keep the simple loop
    assert not dse.recommend_overlap(sys, cfg, 10_000, 1e-3 * dev)
    assert not dse.recommend_overlap(sys, cfg, 10_000, 0.0)
