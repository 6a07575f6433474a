"""prefill_chunk_ms: device time of the prefill-chunk programs per chunk,
in the traced stretch.  The scheduler jits both chunk programs (the
first and the continuing chunk) from lambdas, so their XLA modules are
named `jit__lambda`; no other lambda runs on the serving path."""
from bench import trace_reduce

MODULE = r"^jit__lambda"


def read(run):
    if run.trace is None:
        return None
    got = trace_reduce.module_time(run.trace, MODULE)
    return None if got is None else got[0] / got[1] * 1e3
