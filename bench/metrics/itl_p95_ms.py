"""itl_p95_ms: 95th percentile of the same gaps as itl_p50_ms."""
from bench import measure


def read(run):
    v = measure.percentile(measure.inter_token_gaps(run.records, run.w0,
                                                    run.w1), 95)
    return None if v is None else v * 1e3
