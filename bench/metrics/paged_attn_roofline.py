"""paged_attn_roofline: the least time the decode attention the
window needed could take on this chip, over the device time of the
Pallas paged-decode kernel in the traced stretch.

Needed work (`bench/work.py`): per token decoded in the stretch at
context c, c * (K + V bytes) of KV reads and 4 * n_heads * d_head * c
FLOPs in every layer; least time = max(bytes / HBM bandwidth, FLOPs /
bf16 peak).  Kernel time: the op events of Pallas calls
(`custom_call_target="tpu_custom_call"`) that start inside a launch of
the decode step (`decode_step_ms.MODULE`).  The kernels carry no name of
their own yet; the paged decode kernel is the one Pallas call of the
decode step."""
import re

from bench import trace_reduce

PALLAS = 'custom_call_target="tpu_custom_call"'
DECODE = re.compile(r"^jit__decode_fn\b")


def kernel_s(trace: trace_reduce.Trace) -> float:
    steps = [(e[0], e[3], e[3] + e[4]) for e in
             trace.device("XLA Modules") if DECODE.search(e[2])]
    total = 0
    for chip, t0, dur in [(e[0], e[3], e[4]) for e in
                          trace.device("XLA Ops") if PALLAS in e[2]]:
        if any(c == chip and a <= t0 < b for c, a, b in steps):
            total += dur
    return total / 1e9


def read(run):
    from bench import work
    if run.trace is None:
        return None
    ks = kernel_s(run.trace)
    flops, nbytes, _, n = work.window_work(run.records, run.dm,
                                           run.kv_bytes, run.tw0, run.tw1)
    if ks <= 0 or n == 0:
        return None
    least = max(nbytes / run.peak["hbm_bytes_per_s"],
                flops / run.peak["bf16_flops_per_s"])
    return 100.0 * least / ks
