"""Open-loop traffic from a mix's data file.

A mix (``traffic/<name>.json``) gives the arrival process and lognormal
lengths (median, sigma, clip bounds); the cell (``cells/<workload>.json``)
gives the mean rate.  Keys of a mix:

* ``arrivals``: ``"poisson"``, the one arrival process built;
* ``prompt``, ``output``: length distributions of each request's prompt
  tokens and of the tokens it asks for;
* ``shape_seed``: the seed of the work's shape.

The *shape* of the work is drawn once from ``shape_seed``: how many
requests, their lengths, and the arrival times.  The run's
``--seed`` only reorders it (which request arrives at which of the
shape's arrival times) and draws the token ids, so every seed serves the
same amount of work at the same instants and the runs of a cell differ by
ordering alone.  The reordering stays inside blocks of
``REORDER_BLOCK_S`` seconds of the timeline: every block of every seed
holds the same requests, so the work offered up to any block's end, and
the work still in flight when the window closes, is the same for every
seed, and no request moves by more than a block.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

REORDER_BLOCK_S = 1.0


@dataclasses.dataclass(frozen=True)
class Arrival:
    index: int
    arrival_s: float          # scheduled arrival, seconds after the window opens
    prompt: np.ndarray        # (S,) int32 token ids
    max_new_tokens: int


def _lognormal_lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def shape(mix: dict, rate_per_s: float, seconds: float) -> dict:
    """The window's work in the mix's canonical order, independent of the
    run's seed: prompt lengths, output lengths and arrival times."""
    n = max(1, int(round(rate_per_s * seconds)))
    rng = np.random.default_rng(mix["shape_seed"])
    out = {"prompts": _lognormal_lengths(rng, mix["prompt"], n),
           "outputs": _lognormal_lengths(rng, mix["output"], n)}
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    # a Poisson process conditioned on n arrivals in the window
    out["times"] = np.sort(rng.uniform(0.0, seconds, size=n))
    return out


def _shuffle_within(rng, block: np.ndarray) -> np.ndarray:
    """The indices of ``block`` (sorted), shuffled inside each block."""
    return np.concatenate([rng.permutation(np.flatnonzero(block == b))
                           for b in np.unique(block)])


def generate(mix: dict, rate_per_s: float, seconds: float, seed: int,
             vocab_size: int) -> List[Arrival]:
    """The window's requests, sorted by arrival."""
    sh = shape(mix, rate_per_s, seconds)
    rng = np.random.default_rng(seed)
    block = np.floor(sh["times"] / REORDER_BLOCK_S)
    order = _shuffle_within(rng, block)
    out = []
    for i, (j, t) in enumerate(zip(order, sh["times"])):
        prompt = rng.integers(1, vocab_size, size=int(sh["prompts"][j]),
                              dtype=np.int64)
        out.append(Arrival(i, float(t), prompt.astype(np.int32),
                           int(sh["outputs"][j])))
    return out


def prompt_range(mix: dict):
    """Shortest and longest prompt the mix can send."""
    return int(mix["prompt"]["min"]), int(mix["prompt"]["max"])


def max_seq(mix: dict) -> int:
    """Longest prompt plus longest output: the positions a slot may need."""
    return prompt_range(mix)[1] + int(mix["output"]["max"])


def prefill_buckets(mix: dict, page_size: int) -> List[int]:
    """Page counts of every prefill program the mix can reach.  It depends
    on the mix and the page size alone, never on the seed."""
    lo, hi = prompt_range(mix)
    return list(range(-(-lo // page_size), -(-hi // page_size) + 1))
