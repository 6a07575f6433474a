"""Golden decode tests: prefill+decode through the KVNAND engine must
reproduce the full-forward logits exactly (f32 cache), for every assigned
arch.  This exercises paged pools (global + window ring), RWKV/SSM state
carry, and whisper cross-attention.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_ARCHS, EngineConfig, get_config
from repro.core.engine import KVNANDEngine
from repro.models.registry import Model
from repro.models.transformer import Runtime


def run_golden(arch, n_decode=3, S_prompt=21, page_tokens=8):
    cfg = get_config(arch).reduced()
    cap = (cfg.n_experts / cfg.top_k) if cfg.is_moe else 1.25  # no-drop MoE
    rt = Runtime(moe_capacity=cap)
    m = Model(cfg, rt)
    params = m.init(jax.random.PRNGKey(0))
    eng = KVNANDEngine(
        cfg, EngineConfig(page_tokens=page_tokens, kv_dtype="float32"), rt)
    B = 2
    toks = jax.random.randint(jax.random.PRNGKey(42),
                              (B, S_prompt + n_decode), 0, cfg.vocab_size,
                              jnp.int32)
    extra, prefix = {}, 0
    if cfg.family == "vlm":
        extra["patches"] = jax.random.normal(jax.random.PRNGKey(3),
                                             (B, 8, cfg.d_model))
        prefix += 8
    if cfg.is_encoder_decoder:
        extra["frames"] = jax.random.normal(jax.random.PRNGKey(4),
                                            (B, 8, cfg.d_model))
    prefix += cfg.n_meta_tokens

    logits_full, _ = m.forward(params, {"tokens": toks, **extra})
    lg, cache = eng.prefill(params, {"tokens": toks[:, :S_prompt], **extra},
                            max_context=S_prompt + n_decode + prefix + 2)
    errs = [float(jnp.abs(lg - logits_full[:, S_prompt - 1]).max())]
    for t in range(n_decode):
        lg, cache = eng.decode_step(
            params, cache, toks[:, S_prompt + t:S_prompt + t + 1])
        errs.append(float(jnp.abs(lg - logits_full[:, S_prompt + t]).max()))
    scale = float(jnp.abs(logits_full).max())
    return max(errs) / scale


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_decode_matches_forward_compact(arch):
    assert run_golden(arch) < 2e-4


def test_window_ring_recycling():
    """Decode past the window: ring pages recycle, logits stay faithful."""
    assert run_golden("gemma3-12b", n_decode=8, S_prompt=70,
                      page_tokens=8) < 2e-4


def test_ragged_lengths_path():
    """Non-uniform appends (continuous batching) use the scatter path."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    m = Model(cfg, rt)
    params = m.init(jax.random.PRNGKey(0))
    eng = KVNANDEngine(cfg, EngineConfig(page_tokens=8, kv_dtype="float32"),
                       rt)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              cfg.vocab_size, jnp.int32)
    logits_full, _ = m.forward(params, {"tokens": toks})
    lg, cache = eng.prefill(params, {"tokens": toks[:, :20]}, 30)
    for t in range(3):
        lg, cache = eng.decode_step(params, cache, toks[:, 20 + t:21 + t])
    err = float(jnp.abs(lg - logits_full[:, 22]).max())
    assert err / float(jnp.abs(logits_full).max()) < 2e-4
