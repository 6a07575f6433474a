"""The one traffic generator: a mix file's parameters + a seed -> requests.

A mix file (`bench/traffic/<name>.json`) states the loop and its lengths:

  {"loop": "closed", "clients": 8, "rounds": 6, ...}
  {"loop": "open", "rate_per_s": 2.4, "preroll_s": 10, ...}
  "prompt_tokens" / "output_tokens": {"dist": "uniform", "min", "max"}
                                   | {"dist": "lognormal", "median",
                                      "sigma", "min", "max"}
  "schedule_seed": n      (optional) the order below is drawn from n and
                          not from the run's seed

Every seed gets the same sizes and gaps, in another order, so two seeds
differ in which request comes when and in their token ids, not in how
much work they carry.  With `schedule_seed` every seed gets the same
order too, and two seeds differ only in their token ids (and weights):

- closed loop: round r hands each of the `clients` clients one request;
  the round's (prompt, output) pairs are fixed quantiles of the two
  distributions, and the seed only deals them out to the clients;
- open loop: lengths and inter-arrival gaps are the distributions'
  quantiles at evenly spaced levels, stratified so that every block of
  `BLOCK` consecutive requests holds one value of each stratum, and the
  seed shuffles within each block.  Any stretch of a few dozen arrivals
  then carries nearly the same work and the same offered rate.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np

BLOCK = 8
_PHI = (math.sqrt(5.0) - 1.0) / 2.0       # golden-ratio offsets per round
_PSI = 0.7548776662466927                 # plastic-number offsets


@dataclasses.dataclass
class Req:
    """One request as the client sends it."""
    rid: int
    prompt: List[int]
    max_tokens: int
    due: float = 0.0        # open loop: seconds after the arrivals start
    client: int = 0         # closed loop: which client sends it
    round: int = 0          # closed loop: its place in that client's queue


def quantile(spec: Dict, levels) -> np.ndarray:
    """Lengths of `spec` at the given quantile levels, clipped."""
    lv = np.asarray(levels, np.float64)
    if spec["dist"] == "uniform":
        vals = spec["min"] + lv * (spec["max"] + 1 - spec["min"])
    elif spec["dist"] == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in lv])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(vals), spec["min"], spec["max"]).astype(np.int64)


def rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one stream of one seed (any non-negative int)."""
    s = int(seed) % 2**64
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, stream])


def n_requests(mix: Dict, seconds: float) -> int:
    if mix["loop"] == "closed":
        return mix["clients"] * mix["rounds"]
    span = mix["preroll_s"] + seconds + mix.get("tail_s", 30.0)
    return BLOCK * int(math.ceil(mix["rate_per_s"] * span / BLOCK))


def _stratified(values: np.ndarray, order: np.random.Generator):
    """Sorted `values` (length a multiple of BLOCK) dealt so that block b
    takes the b-th value of every stratum, shuffled within the block."""
    v = np.sort(values)
    nb = len(v) // BLOCK
    out = v.reshape(BLOCK, nb).T.copy()          # [block, stratum]
    for row in out:
        order.shuffle(row)
    return out.reshape(-1)


def make_requests(mix: Dict, seed: int, seconds: float,
                  vocab: int) -> List[Req]:
    """Requests in sending order.  Closed loop: client c sends its
    requests in round order, each after the previous one ended.  Open
    loop: `due` is the request's send time, counted from the first
    arrival."""
    n = n_requests(mix, seconds)
    order = rng(mix.get("schedule_seed", seed), 0)
    ids = rng(seed, 1)
    if mix["loop"] == "closed":
        nc = mix["clients"]
        plens, olens, clients, rounds = [], [], [], []
        for r in range(mix["rounds"]):
            u = (0.5 + r * _PHI) % 1.0
            v = (0.5 + r * _PSI) % 1.0
            k = np.arange(nc)
            p = quantile(mix["prompt_tokens"], (k + u) / nc)
            o = quantile(mix["output_tokens"], (((nc - 1 - k + r) % nc)
                                                + v) / nc)
            plens += p.tolist()
            olens += o.tolist()
            clients += order.permutation(nc).tolist()
            rounds += [r] * nc
        return [Req(rid=i, prompt=ids.integers(0, vocab, plens[i]).tolist(),
                    max_tokens=int(olens[i]), client=int(clients[i]),
                    round=rounds[i])
                for i in range(n)]
    if mix["loop"] != "open":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    lv = (np.arange(n) + 0.5) / n
    plens = _stratified(quantile(mix["prompt_tokens"], lv), order)
    olens = _stratified(quantile(mix["output_tokens"], lv), order)
    gaps = _stratified(-np.log1p(-lv) / mix["rate_per_s"], order)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [Req(rid=i, prompt=ids.integers(0, vocab, int(plens[i])).tolist(),
                max_tokens=int(olens[i]), due=float(due[i]))
            for i in range(n)]


def warmup_requests(mix: Dict, seed: int, vocab: int) -> List[Req]:
    """Open loop: two requests that run every program the window runs
    before it opens: a prompt of the mix's longest length (so the first
    and the continuing prefill-chunk programs run) and a short one, two
    output tokens each (so the decode step runs)."""
    ids = rng(seed, 2)
    lens = [mix["prompt_tokens"]["max"], mix["prompt_tokens"]["min"]]
    return [Req(rid=-1 - i, prompt=ids.integers(0, vocab, n).tolist(),
                max_tokens=2) for i, n in enumerate(lens)]
