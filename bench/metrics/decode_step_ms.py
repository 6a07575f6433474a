"""decode_step_ms: device time of the jitted decode step (the XLA module
of the scheduler's `_decode_fn`) per launch, in the traced stretch."""
from bench import trace_reduce

MODULE = r"^jit__decode_fn\b"


def read(run):
    if run.trace is None:
        return None
    got = trace_reduce.module_time(run.trace, MODULE)
    return None if got is None else got[0] / got[1] * 1e3
