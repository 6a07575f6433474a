"""Training integration: learning, microbatch equivalence, quantized
forward, serving scheduler round-trip."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import EngineConfig, get_config
from repro.data.pipeline import DataConfig, DataIterator, make_source
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import (
    grads_and_metrics, init_train_state, make_train_step,
)


def test_loss_decreases():
    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    m = Model(cfg, rt)
    acfg = AdamWConfig(lr=3e-3, warmup_steps=5, total_steps=80)
    state = init_train_state(m.init(jax.random.PRNGKey(0)), acfg)
    step = jax.jit(make_train_step(cfg, rt, acfg, EngineConfig()))
    it = DataIterator(make_source(DataConfig(
        seq_len=64, global_batch=16, vocab_size=cfg.vocab_size)))
    first = last = None
    for _ in range(80):
        state, metrics = step(state, {k: jnp.asarray(v)
                                      for k, v in next(it).items()})
        if first is None:
            first = float(metrics["loss"])
        last = float(metrics["loss"])
    assert last < first - 1.5, (first, last)


def test_microbatch_grads_match_full_batch():
    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    m = Model(cfg, rt)
    params = m.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                     cfg.vocab_size, jnp.int32),
        "labels": jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0,
                                     cfg.vocab_size, jnp.int32)}
    g1, m1 = jax.jit(lambda p, b: grads_and_metrics(p, b, cfg, rt, "none",
                                                    1))(params, batch)
    g2, m2 = jax.jit(lambda p, b: grads_and_metrics(p, b, cfg, rt, "none",
                                                    2))(params, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-4, rtol=1e-3)


def test_continuous_batching_matches_sequential():
    """Scheduler outputs == one-at-a-time greedy decoding per request."""
    from repro.serving.scheduler import ContinuousBatcher, Request
    from repro.core.engine import KVNANDEngine
    from repro.serving.sampler import sample

    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    m = Model(cfg, rt)
    params = m.init(jax.random.PRNGKey(0))
    prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9, 7, 9]]

    # sequential reference (greedy)
    eng = EngineConfig(page_tokens=8, kv_dtype="float32")
    ref_engine = KVNANDEngine(cfg, eng, rt)
    ref_out = []
    for p in prompts:
        toks = jnp.asarray(p, jnp.int32)[None]
        lg, cache = ref_engine.prefill(params, {"tokens": toks}, 64)
        outs = []
        tok = sample(lg, jax.random.PRNGKey(0), true_vocab=cfg.vocab_size)
        for _ in range(6):
            outs.append(int(tok[0]))
            lg, cache = ref_engine.decode_step(params, cache, tok[:, None])
            tok = sample(lg, jax.random.PRNGKey(0),
                         true_vocab=cfg.vocab_size)
        ref_out.append(outs)

    batcher = ContinuousBatcher(
        cfg, params, batch_slots=2, max_context=64,
        eng=EngineConfig(page_tokens=8, kv_dtype="float32"))
    for uid, p in enumerate(prompts):
        batcher.submit(Request(uid=uid, prompt=list(p), max_new=6))
    done = batcher.run_to_completion()
    for uid, outs in enumerate(ref_out):
        assert done[uid].output[:6] == outs, (uid, done[uid].output, outs)


def test_quantized_decode_close_to_fp():
    from repro.core.engine import KVNANDEngine
    from repro.core.quant import quantize_params
    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    m = Model(cfg, rt)
    params = m.init(jax.random.PRNGKey(0))
    qparams = quantize_params(params, "w8a8")
    eng = KVNANDEngine(cfg, EngineConfig(page_tokens=8), rt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab_size, jnp.int32)
    lg_fp, _ = eng.prefill(params, {"tokens": toks}, 20)
    lg_q, _ = eng.prefill(qparams, {"tokens": toks}, 20)
    scale = float(jnp.abs(lg_fp).max())
    assert float(jnp.abs(lg_fp - lg_q).max()) / scale < 0.15
