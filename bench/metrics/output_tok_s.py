"""output_tok_s: tokens streamed to clients in the window / its seconds
(host clock, every token of every request)."""
from bench import measure


def read(run):
    return measure.rate(run.records, run.w0, run.w1)
