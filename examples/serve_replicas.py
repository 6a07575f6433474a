"""Multi-replica serving example: a `ReplicaRouter` spreading requests
over a fleet of `KVNANDServer` replicas, then the same fleet running
disaggregated — prefill on replica 0, KV pages migrated as `KVEnvelope`
wire bytes into a decode replica, token-identical to a single server.

    PYTHONPATH=src python examples/serve_replicas.py
"""
import jax
import numpy as np

from repro.configs import EngineConfig, get_config
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.api import KVNANDServer, SamplingParams, ServerConfig
from repro.serving.router import ReplicaRouter


def _fleet(n, cfg, params, rt):
    eng = EngineConfig(page_tokens=16, shared_pool=True, total_pages=48)
    sc = ServerConfig(arch="qwen1.5-0.5b", reduced=True, engine=eng,
                      batch_slots=2, max_context=64,
                      prefill_chunk_tokens=16, seed=7)
    return [KVNANDServer(sc, cfg=cfg, params=params, rt=rt)
            for _ in range(n)]


def main():
    # one set of weights, shared by every replica (a real fleet would
    # device_put per accelerator — see replica.build_replica)
    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    params = Model(cfg, rt).init(jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    sysp = rng.integers(1, cfg.vocab_size, 20).tolist()
    prompts = [sysp + rng.integers(1, cfg.vocab_size,
                                   int(rng.integers(2, 8))).tolist()
               for _ in range(6)]
    sp = SamplingParams(max_new_tokens=6, temperature=0.8, seed=3)

    # --- routed mode: least-loaded spread + cross-replica prefix index
    router = ReplicaRouter(_fleet(3, cfg, params, rt))
    uids = [router.submit(p, sp) for p in prompts]
    router.run()
    homes = [router.replica_of(u) for u in uids]
    assert len(set(homes)) >= 2, "fleet never spread"
    print(f"routed: {len(uids)} requests over replicas {sorted(set(homes))}, "
          f"{router.stats['prefix_published_pages']} prefix pages published "
          f"to the cross-replica index")

    # --- disaggregated mode: prefill on replica 0, decode elsewhere
    fleet = _fleet(3, cfg, params, rt)
    disagg = ReplicaRouter(fleet, disaggregate=True)
    solo = _fleet(1, cfg, params, rt)[0]
    for i, p in enumerate(prompts):
        disagg.submit(p, sp, uid=i)
        solo.submit(p, sp, uid=i)
    disagg.run()
    solo.run()
    for i in range(len(prompts)):
        assert disagg.output(i).token_ids == solo.output(i).token_ids, \
            f"migrated request {i} diverged from single-server run"
    mig = disagg.stats
    print(f"disaggregated: {mig['migrations']} migrations, "
          f"{mig['migration_bytes'] // mig['migrations']} wire bytes each, "
          f"outputs token-identical to one server")
    print("serve_replicas example complete")


if __name__ == "__main__":
    main()
