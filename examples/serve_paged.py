"""Serving example: mixed per-request sampling over the shared-pool
paged engine, streamed token by token through the `KVNANDServer` facade.

    PYTHONPATH=src python examples/serve_paged.py
"""
import numpy as np

from repro.configs import EngineConfig
from repro.serving.api import KVNANDServer, SamplingParams, ServerConfig


def main():
    server = KVNANDServer(ServerConfig(
        arch="qwen1.5-0.5b", reduced=True,
        engine=EngineConfig(page_tokens=16, shared_pool=True),
        batch_slots=3, max_context=128, prefill_chunk_tokens=32))

    rng = np.random.default_rng(0)
    vocab = server.cfg.vocab_size
    sysp = rng.integers(1, vocab, 24).tolist()   # shared system prompt
    mixes = [SamplingParams(max_new_tokens=12),                  # greedy
             SamplingParams(max_new_tokens=12, temperature=0.8,
                            top_p=0.9, seed=7),                  # nucleus
             SamplingParams(max_new_tokens=12, temperature=1.2,
                            top_k=40, seed=11, logprobs=True)]   # top-k
    for i in range(6):
        tail = rng.integers(1, vocab, int(rng.integers(3, 10))).tolist()
        server.submit(sysp + tail, mixes[i % len(mixes)])

    streamed = {}
    for ev in server.stream():                   # tokens as they land
        streamed.setdefault(ev.uid, []).append(ev.token)

    outs = server.outputs()
    assert len(outs) == 6
    for o in outs:
        assert streamed[o.uid] == o.token_ids    # stream == final output
        print(f"req {o.uid}: {len(o.token_ids)} tokens "
              f"({o.finish_reason}, ttft {o.ttft * 1e3:.0f} ms) "
              f"-> {o.token_ids[:6]}...")
    st = server.stats
    print(f"prefix cache served {st['prefix_hit_pages']} of "
          f"{st['prompt_pages']} prompt pages; "
          f"{st['compiles']} compiles for 3 distinct sampling configs")
    print("serve_paged example complete")


if __name__ == "__main__":
    main()
