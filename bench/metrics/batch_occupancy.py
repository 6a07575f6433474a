"""batch_occupancy: decode rows used per scheduler step over the window,
as a share of the slots: the scheduler's own counters (`decode_tokens`,
`steps`), differenced across the window."""


def read(run):
    steps = run.stats1["steps"] - run.stats0["steps"]
    if steps <= 0:
        return None
    rows = run.stats1["decode_tokens"] - run.stats0["decode_tokens"]
    return 100.0 * rows / (steps * run.batch_slots)
