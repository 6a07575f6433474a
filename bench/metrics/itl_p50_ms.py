"""itl_p50_ms: median of every inter-token gap of every request whose
later token arrived in the window (host clock)."""
from bench import measure


def read(run):
    v = measure.percentile(measure.inter_token_gaps(run.records, run.w0,
                                                    run.w1), 50)
    return None if v is None else v * 1e3
