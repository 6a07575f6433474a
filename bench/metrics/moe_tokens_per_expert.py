"""moe_tokens_per_expert: token-expert pairs of the window's decode steps
that landed on the experts this chip holds (the scheduler's
`moe_pairs_held` counter), per decode step (`decode_steps`), per held
expert and per MoE layer (from the configuration's reference sizes): how
many rows each held expert's weights serve when they are read.  A
program or model without these counters reads None."""


def read(run):
    keys = ("moe_pairs_held", "decode_steps")
    if not all(k in run.stats0 and k in run.stats1 for k in keys):
        return None
    steps = run.stats1["decode_steps"] - run.stats0["decode_steps"]
    layers = getattr(run.dm, "L", 0) - getattr(run.dm, "n_dense", 0)
    held = getattr(run.dm, "E_held", 0)
    if steps <= 0 or layers <= 0 or held <= 0:
        return None
    pairs = run.stats1["moe_pairs_held"] - run.stats0["moe_pairs_held"]
    return pairs / (steps * held * layers)
