"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and draws its requests from ``--seed``.

Every seed gets the same multiset of sizes and gaps in another order:
lengths and gaps are quantiles of their distribution at evenly spaced
levels, in blocks, each block shuffled by the seed. Only the order and
the prompt tokens change with the seed, so runs of different seeds do
the same amount of work.

Keys of a mix (all required unless marked optional):

  loop           "closed": ``clients`` clients, each sending its next
                 request when its last one completes; "open": arrivals
                 at ``rate_per_s`` whatever the system does
  clients        closed loop: number of clients
  rate_per_s     open loop: mean arrival rate (Poisson)
  lead_in_s      open loop: seconds of traffic before the window opens
  slots          running batch size of the scheduler
  cache_len      positions per slot
  admission      scheduler admission policy
  decode_chunk   decode steps per fused call
  prompt_len     {"values": [...], "weights": [...]}
  output_len     {"median": m, "sigma": s, "min": a, "max": b}: lognormal,
                 truncated to [a, b] (no mass piles up at a bound)
  topics         topic names; each request draws one, uniformly
  block          requests per stratified block
  max_group      {prompt length: largest number of same-length admissions
                 one scheduler pass can make}; set-up warms every size,
                 and in a closed loop also the first wave (all slots)
  max_queue      longest queue the admission ranking is warmed for
  check_steps    decode steps from the first that the reference replays
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

from synthetic import SyntheticLM

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class Req:
    index: int
    topic: str
    prompt: np.ndarray
    max_new: int
    due_s: Optional[float] = None   # open loop: seconds after the door opens


def load_mix(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    mix["max_group"] = {int(k): int(v) for k, v in mix["max_group"].items()}
    return mix


def _levels(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _blocks(rng, n: int, block: int, draw) -> np.ndarray:
    """n values: blocks of `block` quantiles, each block shuffled."""
    out = []
    while sum(len(b) for b in out) < n:
        out.append(rng.permutation(draw(_levels(block))))
    return np.concatenate(out)[:n]


def output_lengths(mix: Dict, rng, n: int) -> np.ndarray:
    o = mix["output_len"]
    nd = NormalDist()

    lo = nd.cdf(np.log(o["min"] / o["median"]) / o["sigma"])
    hi = nd.cdf(np.log(o["max"] / o["median"]) / o["sigma"])

    def draw(u):
        z = np.array([nd.inv_cdf(lo + x * (hi - lo)) for x in u])
        v = np.round(o["median"] * np.exp(o["sigma"] * z))
        return np.clip(v, o["min"], o["max"]).astype(int)
    return _blocks(rng, n, mix["block"], draw)


def prompt_lengths(mix: Dict, rng, n: int) -> np.ndarray:
    p = mix["prompt_len"]
    vals, w = np.asarray(p["values"]), np.asarray(p["weights"], float)

    def draw(u):
        edges = np.cumsum(w / w.sum())
        return vals[np.minimum(np.searchsorted(edges, u), len(vals) - 1)]
    return _blocks(rng, n, mix["block"], draw)


def topics(mix: Dict, rng, n: int) -> List[str]:
    names = mix["topics"]
    idx = _blocks(rng, n, mix["block"],
                  lambda u: np.floor(u * len(names)).astype(int))
    return [names[i] for i in idx]


def arrival_times(mix: Dict, rng, horizon_s: float) -> np.ndarray:
    """Open loop: Poisson arrivals at rate_per_s over [0, horizon_s)."""
    rate = float(mix["rate_per_s"])
    n = int(math.ceil(rate * horizon_s))
    gaps = _blocks(rng, n, mix["block"], lambda u: -np.log1p(-u) / rate)
    t = np.cumsum(gaps)
    return t[t < horizon_s]


def pool_size(mix: Dict, seconds: float) -> int:
    """Closed loop: requests to draw, enough for the window at any rate
    the chip can reach (every client decoding one token per step of
    2 ms), rounded up to whole blocks."""
    steps = seconds / 0.002 + mix["output_len"]["max"]
    n = mix["clients"] + int(steps * mix["slots"] / mix["output_len"]["min"])
    return -(-n // mix["block"]) * mix["block"]


def generate(mix: Dict, vocab_size: int, seed: int,
             seconds: float) -> List[Req]:
    """The requests of one run, in sending order (closed loop) or in
    arrival order (open loop)."""
    rng = np.random.default_rng(int(seed) % (1 << 64))
    if mix["loop"] == "open":
        due = arrival_times(mix, rng, mix["lead_in_s"] + seconds)
        n = len(due)
    elif mix["loop"] == "closed":
        due, n = None, pool_size(mix, seconds)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    outs = output_lengths(mix, rng, n)
    plens = prompt_lengths(mix, rng, n)
    tops = topics(mix, rng, n)
    prompts: List[Optional[np.ndarray]] = [None] * n
    for name in mix["topics"]:
        lm = SyntheticLM(vocab_size, name=name)
        for length in sorted(set(int(x) for x in plens)):
            rows = [i for i in range(n)
                    if tops[i] == name and plens[i] == length]
            if rows:
                for i, p in zip(rows, lm.sample(rng, len(rows), length)):
                    prompts[i] = p
    seen = set()
    for p in prompts:
        key = p.tobytes()
        if key in seen:
            raise RuntimeError("two requests drew the same prompt")
        seen.add(key)
    return [Req(i, tops[i], prompts[i], int(outs[i]),
                None if due is None else float(due[i])) for i in range(n)]


def warm_shapes(mix: Dict) -> List[tuple]:
    """Every (group size, prompt length) that a scheduler pass can
    prefill under this mix."""
    out = {(g, length) for length in mix["max_group"]
           for g in range(1, mix["max_group"][length] + 1)}
    if mix["loop"] == "closed":
        out |= {(min(mix["slots"], mix["clients"]), length)
                for length in mix["max_group"]}
    return sorted(out)
