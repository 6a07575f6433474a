"""Vectorized sampler: per-row greedy/temperature/top-k/top-p masking,
exact no-op neutrals inside mixed batches, pad-id exclusion at any
temperature (property-swept), and per-request PRNG streams."""
import jax
import jax.numpy as jnp
import numpy as np

from tests._hypothesis_compat import given, settings, st

from repro.serving.sampler import (SamplingParams, request_keys, sample,
                                   sample_with_logprobs)

V, TRUE_V = 48, 40


def _logits(seed, b=4, v=V, tempting_pad=True):
    lg = jax.random.normal(jax.random.PRNGKey(seed), (b, v)) * 3.0
    if tempting_pad:
        # make the padding lanes the LARGEST raw logits: any masking slip
        # would sample them immediately
        lg = lg.at[:, TRUE_V:].set(50.0)
    return lg


def _keys(b, pos=0):
    return request_keys(np.arange(1, b + 1, dtype=np.uint32),
                        np.full(b, pos, np.int32))


def test_sampling_params_validation():
    import pytest
    SamplingParams()                       # defaults are valid
    with pytest.raises(ValueError):
        SamplingParams(temperature=-0.1)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    with pytest.raises(ValueError):
        SamplingParams(max_new_tokens=0)
    assert SamplingParams(stop_token_ids=[3, 7]).stop_token_ids == (3, 7)


def test_topk_zero_rows_are_exact_noops_in_vectorized_batch():
    """A top_k=0 row in a batch whose neighbors use top-k must sample the
    IDENTICAL token to a run with no top-k at all (same keys)."""
    lg, keys = _logits(0), _keys(4)
    temps = jnp.ones(4)
    mixed = sample(lg, keys, true_vocab=TRUE_V, temperature=temps,
                   top_k=jnp.array([0, 5, 0, 2], jnp.int32))
    plain = sample(lg, keys, true_vocab=TRUE_V, temperature=temps, top_k=0)
    assert int(mixed[0]) == int(plain[0])
    assert int(mixed[2]) == int(plain[2])


def test_topp_one_rows_are_exact_noops_in_vectorized_batch():
    lg, keys = _logits(1), _keys(4)
    temps = jnp.ones(4)
    mixed = sample(lg, keys, true_vocab=TRUE_V, temperature=temps,
                   top_p=jnp.array([1.0, 0.3, 1.0, 0.5]))
    plain = sample(lg, keys, true_vocab=TRUE_V, temperature=temps)
    assert int(mixed[0]) == int(plain[0])
    assert int(mixed[2]) == int(plain[2])


def test_greedy_rows_ignore_noise_and_neighbors():
    """temperature=0 rows take the raw argmax even when every neighbor
    runs hot."""
    lg, keys = _logits(2), _keys(4)
    toks = sample(lg, keys, true_vocab=TRUE_V,
                  temperature=jnp.array([0.0, 2.0, 0.0, 5.0]))
    ref = jnp.argmax(jnp.where(jnp.arange(V) >= TRUE_V, -1e9, lg), axis=-1)
    assert int(toks[0]) == int(ref[0])
    assert int(toks[2]) == int(ref[2])


def test_topk_restricts_to_k_largest():
    lg = _logits(3, b=64)
    keys = _keys(64, pos=5)
    k = 3
    toks = np.asarray(sample(lg, keys, true_vocab=TRUE_V, temperature=1.5,
                             top_k=k))
    top3 = np.argsort(-np.asarray(lg[:, :TRUE_V]), axis=-1)[:, :k]
    for b in range(64):
        assert toks[b] in top3[b], (b, toks[b], top3[b])


def test_topp_keeps_minimal_nucleus():
    """A hand-built distribution: p = [.5, .3, .15, .05]; top_p=0.7 keeps
    exactly {0, 1} (mass before token 2 is 0.8 >= 0.7)."""
    probs = np.array([0.5, 0.3, 0.15, 0.05])
    lg = jnp.broadcast_to(jnp.log(jnp.asarray(probs))[None], (256, 4))
    keys = _keys(256, pos=9)
    toks = np.asarray(sample(lg, keys, true_vocab=4, temperature=1.0,
                             top_p=0.7))
    assert set(toks.tolist()) <= {0, 1}
    assert len(set(toks.tolist())) == 2    # genuinely samples, not argmax

    # tiny top_p degenerates to argmax for every row
    toks = np.asarray(sample(lg, keys, true_vocab=4, temperature=1.0,
                             top_p=1e-6))
    assert set(toks.tolist()) == {0}


def test_topk_then_topp_compose_sequentially():
    """Standard composition: top-p runs on the RENORMALIZED top-k
    survivors.  p = [.4, .3, .2, .1] with top_k=2, top_p=0.5: top-2
    renormalizes to [.571, .429], whose nucleus at 0.5 is {0} alone —
    token 1 must never appear (an independent-masks implementation
    would sample it ~43% of the time)."""
    probs = np.array([0.4, 0.3, 0.2, 0.1])
    lg = jnp.broadcast_to(jnp.log(jnp.asarray(probs))[None], (256, 4))
    keys = _keys(256, pos=3)
    toks = np.asarray(sample(lg, keys, true_vocab=4, temperature=1.0,
                             top_k=2, top_p=0.5))
    assert set(toks.tolist()) == {0}


@settings(max_examples=25, deadline=None)   # the first example compiles
@given(seed=st.integers(0, 2**16),
       temp=st.floats(0.0, 4.0),
       tk=st.integers(0, 12),
       tp=st.floats(0.05, 1.0))
def test_pad_ids_never_sampled(seed, temp, tk, tp):
    """Vocab padding (ids >= true_vocab) is unsampleable at ANY
    temperature / filter combination, even when the pad lanes hold the
    largest raw logits."""
    lg = _logits(seed, b=8)
    keys = _keys(8, pos=seed % 97)
    toks = np.asarray(sample(lg, keys, true_vocab=TRUE_V,
                             temperature=jnp.full(8, temp),
                             top_k=jnp.full(8, tk, jnp.int32),
                             top_p=jnp.full(8, tp)))
    assert (toks < TRUE_V).all(), (temp, tk, tp, toks)


def test_pad_ids_never_sampled_at_extreme_temperature():
    """Huge temperatures flatten real logits toward 0; the pad floor must
    stay temperature-independent (masked after scaling) or noise would
    lift padding into range."""
    lg = _logits(11, b=16)
    keys = _keys(16, pos=1)
    for temp in (1e-4, 1.0, 1e4, 1e9):
        toks = np.asarray(sample(lg, keys, true_vocab=TRUE_V,
                                 temperature=temp))
        assert (toks < TRUE_V).all(), temp


def test_request_streams_independent_of_batch_composition():
    """Row i's draw depends only on (seed, position): the same request
    sampled alone or inside a crowd gets the same token."""
    lg = _logits(4, b=3, tempting_pad=False)
    seeds = np.array([7, 7, 9], np.uint32)
    pos = np.array([2, 5, 2], np.int32)
    keys = request_keys(seeds, pos)
    batch = sample(lg, keys, true_vocab=TRUE_V, temperature=1.0)
    for i in range(3):
        solo = sample(lg[i:i + 1], request_keys(seeds[i:i + 1],
                                                pos[i:i + 1]),
                      true_vocab=TRUE_V, temperature=1.0)
        assert int(solo[0]) == int(batch[i])
    # same seed, different position -> a fresh draw (a stream, not a
    # constant); rows 0 and 1 share a seed yet may differ
    k2 = request_keys(seeds[:1], np.array([6], np.int32))
    assert k2.shape == (1, 2)


def test_single_key_matches_legacy_categorical_stream():
    """The legacy surface (one batch-shared key, scalar knobs) must keep
    its exact token stream: gumbel-argmax == jax.random.categorical."""
    lg = _logits(5, tempting_pad=False)
    key = jax.random.PRNGKey(42)
    got = sample(lg, key, true_vocab=TRUE_V, temperature=0.7)
    want = jax.random.categorical(
        key, jnp.where(jnp.arange(V) >= TRUE_V, -1e9,
                       lg.astype(jnp.float32)) / 0.7, axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_logprobs_are_raw_distribution_scores():
    """Returned logprobs come from the pad-masked RAW distribution —
    invariant to temperature/filters — and match log_softmax exactly."""
    lg = _logits(6)
    keys = _keys(4)
    toks, lps = sample_with_logprobs(lg, keys, true_vocab=TRUE_V,
                                     temperature=jnp.array([0.0, 1.0,
                                                            2.0, 0.5]))
    masked = jnp.where(jnp.arange(V) >= TRUE_V, -1e9,
                       lg.astype(jnp.float32))
    ref = jax.nn.log_softmax(masked, axis=-1)
    for i in range(4):
        assert float(lps[i]) == float(ref[i, int(toks[i])])
        assert float(lps[i]) <= 0.0
