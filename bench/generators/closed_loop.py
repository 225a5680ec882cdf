"""Closed-loop traffic: a fixed number of clients, each sending its next
request as soon as its previous one has finished.

Every seed gets the same prompt and output lengths (the quantiles of
the mix's clipped lognormal distributions, one per request of the pool)
in the same fixed order, which spreads long and short requests evenly
over the run, and token ids of its own; after the pool's first round
the order repeats.  So two seeds ask for the same work in the same
order and differ only in its content: with a closed loop the schedule
of prefill and decode ticks is the same for every seed, and runs differ
by the time each tick takes, not by which requests met in a tick.

Prompts are unique: no two prompts of a run (up to the vocabulary's
size) open with the same token, so the prefix cache never matches one.

Mix parameters (a JSON file under ``bench/mixes/``)::

    {"generator": "closed_loop", "loop": "closed", "clients": 24,
     "pool": 24,
     "prompt": {"dist": "lognormal", "median": 128, "sigma": 0.6,
                "min": 32, "max": 512},
     "output": {"dist": "lognormal", "median": 512, "sigma": 0.6,
                "min": 128, "max": 1536},
     "follow_to_end": false, "check_requests": 4}
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


def lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles ``(i + 0.5) / n`` of a
    lognormal with the given median and sigma, clipped to [min, max]."""
    if spec.get("dist") != "lognormal":
        raise ValueError(f"unknown length distribution {spec.get('dist')!r}")
    nd = NormalDist()
    z = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    vals = [spec["median"] * math.exp(spec["sigma"] * zi) for zi in z]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def spread_order(n: int) -> np.ndarray:
    """A fixed order of ``n`` sorted lengths that spreads them evenly:
    request ``i`` takes the rank of ``frac(i * golden ratio)``."""
    phi = (1.0 + math.sqrt(5.0)) / 2.0
    return np.argsort(np.argsort((np.arange(n) * phi) % 1.0))


@dataclasses.dataclass
class Request:
    index: int                 # position in the send order
    prompt: np.ndarray         # int32 token ids
    max_new_tokens: int


class Traffic:
    """The seed's request sequence, drawn lazily in send order."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix = mix
        self.clients = int(mix["clients"])
        self.vocab = int(vocab)
        pool = int(mix["pool"])
        self.prompt_lens = lognormal_quantiles(mix["prompt"], pool)
        self.output_lens = lognormal_quantiles(mix["output"], pool)
        # prompt lengths in the spread order, output lengths in the
        # same order backwards, so the two are not paired rank to rank
        self._p_order = spread_order(pool)
        self._o_order = self._p_order[::-1]
        self._rng = np.random.default_rng(seed)
        self._first = self._rng.permutation(self.vocab)
        self._sent = 0

    @property
    def prompt_range(self) -> tuple[int, int]:
        return int(self.mix["prompt"]["min"]), int(self.mix["prompt"]["max"])

    @property
    def output_range(self) -> tuple[int, int]:
        return int(self.mix["output"]["min"]), int(self.mix["output"]["max"])

    def next(self) -> Request:
        i = self._sent
        pool = len(self._p_order)
        plen = int(self.prompt_lens[self._p_order[i % pool]])
        olen = int(self.output_lens[self._o_order[i % pool]])
        prompt = self._rng.integers(0, self.vocab, plen).astype(np.int32)
        prompt[0] = self._first[i % self.vocab]
        self._sent += 1
        return Request(i, prompt, olen)


def make(mix: dict, seed: int, vocab: int) -> Traffic:
    return Traffic(mix, seed, vocab)
