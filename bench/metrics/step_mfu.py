"""step_mfu: model FLOPs of the tokens that reached clients in the
traced stretch (2 per matmul parameter, the head where logits are due,
attention over live context: `bench/work.py`) over the stretch's
seconds times the chip's bf16 peak."""
from bench import work


def read(run):
    if run.tw0 is None:
        return None
    _, _, flops, _ = work.window_work(run.records, run.dm, run.kv_bytes,
                                      run.tw0, run.tw1)
    if not flops:
        return None
    return 100.0 * flops / ((run.tw1 - run.tw0)
                            * run.peak["bf16_flops_per_s"])
