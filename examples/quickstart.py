"""Quickstart: train a tiny LM on synthetic data, then serve it through
the request-centric `KVNANDServer` API — the full loop in ~2 minutes on
CPU.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import EngineConfig, get_config
from repro.data.pipeline import DataConfig, DataIterator, make_source
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.api import KVNANDServer, SamplingParams, ServerConfig
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_train_state, make_train_step


def main():
    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    model = Model(cfg, rt)
    params = model.init(jax.random.PRNGKey(0))
    print(f"model: {cfg.name} ({cfg.param_count() / 1e6:.2f}M params)")

    # -- train ----------------------------------------------------------
    acfg = AdamWConfig(lr=3e-3, warmup_steps=10, total_steps=150)
    state = init_train_state(params, acfg)
    step = jax.jit(make_train_step(cfg, rt, acfg, EngineConfig()))
    it = DataIterator(make_source(DataConfig(
        seq_len=64, global_batch=16, vocab_size=cfg.vocab_size)))
    for i in range(150):
        state, metrics = step(state, {k: jnp.asarray(v)
                                      for k, v in next(it).items()})
        if i % 25 == 0:
            print(f"  step {i:3d}  loss {float(metrics['loss']):.3f}")
    print(f"  final loss {float(metrics['loss']):.3f} "
          f"(random = {jnp.log(cfg.vocab_size):.2f})")

    # -- serve the trained weights through the KVNAND engine -------------
    # KVNANDServer owns engine + scheduler construction; pass the freshly
    # trained params instead of letting it initialize its own
    server = KVNANDServer(
        ServerConfig(engine=EngineConfig(page_tokens=8),
                     batch_slots=1, max_context=64),
        cfg=cfg, params=state.params, rt=rt)
    out = server.generate([[5, 17, 42, 7]],
                          SamplingParams(max_new_tokens=24))[0]
    print(f"generated ({out.finish_reason}, "
          f"ttft {out.ttft * 1e3:.0f} ms): {out.token_ids}")
    # the synthetic stream is 80% next = perm[cur]; a trained model locks on
    src = it.source
    toks = out.token_ids
    follows = sum(int(src.perm[a]) == b for a, b in zip(toks, toks[1:]))
    print(f"{follows}/{len(toks) - 1} transitions follow the learned chain")


if __name__ == "__main__":
    main()
