"""Closed-loop traffic from a mix file of parameters.

A mix (``bench/traffic/<name>.json``) states a number of clients and the
length distributions of prompts and outputs. From it this module builds a
*deck*: a fixed multiset of (prompt, output) length pairs taken at evenly
spaced quantiles of the clipped lognormals. Requests take the deck round
after round, each round in a fixed shuffled order that is the same for
every seed; the run's seed draws only the prompt tokens (and, elsewhere,
the weights). So every seed serves the same sizes in the same order, and
the engine, whose schedule depends on lengths alone, meets the same
programs: a seed changes what is computed, never how much or in which
programs.

The lognormal parameterisation (mean and coefficient of variation, then
clipped) is copied from ``src/repro/serving/traces.py`` (``_lognormal``) and
``src/repro/serving/loadgen.py``; the Table-1 statistics of the DuetServe
paper (Azure conversation: prompt mean 1155, CV 1.1; output mean 211,
CV 0.9) come from ``traces.TRACES``. They are copied so that the yardstick
does not move when the program changes.

"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import List

import numpy as np

# substream tags under the run seed
_ORDER, _TOKENS, _SAMPLE = 1, 2, 3


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """Generator for one named substream of a (possibly > 32-bit) seed."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


@dataclass(frozen=True)
class LengthSpec:
    mean: float
    cv: float
    min: int
    max: int

    def quantiles(self, n: int) -> np.ndarray:
        """``n`` lengths at the quantiles (i + 0.5) / n of the lognormal with
        this mean and CV, clipped to [min, max] and rounded."""
        sigma2 = math.log(1.0 + self.cv * self.cv)
        mu = math.log(self.mean) - sigma2 / 2.0
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        x = np.clip(np.exp(mu + math.sqrt(sigma2) * z), self.min, self.max)
        return np.round(x).astype(np.int64)


@dataclass(frozen=True)
class Mix:
    name: str
    clients: int
    deck: int
    prompt: LengthSpec
    output: LengthSpec

    @classmethod
    def load(cls, path: Path) -> "Mix":
        d = json.loads(Path(path).read_text())
        return cls(name=Path(path).stem, clients=int(d["clients"]),
                   deck=int(d["deck"]), prompt=LengthSpec(**d["prompt"]),
                   output=LengthSpec(**d["output"]))

    def pairs(self) -> np.ndarray:
        """The deck: (deck, 2) int array of (prompt_len, output_len). Prompt
        and output quantiles are paired by one fixed permutation, the same
        for every mix and seed."""
        p = self.prompt.quantiles(self.deck)
        o = self.output.quantiles(self.deck)
        o = o[np.random.default_rng(0).permutation(self.deck)]
        return np.stack([p, o], axis=1)


class RequestStream:
    """The run's sequence of request lengths: rounds of the deck, each in a
    fixed shuffled order, the same for every seed. ``tokens`` draws prompt
    ids from the seed, a separate substream per pass, so two passes over
    the same lengths share no prompt."""

    def __init__(self, mix: Mix, seed: int):
        self.mix = mix
        self.seed = seed
        self._deck = mix.pairs()
        self._order: List[int] = []
        self._rounds = 0

    def lengths(self, i: int):
        """(prompt_len, output_len) of the i-th request of the run."""
        while i >= len(self._order):
            perm = seed_rng(0, _ORDER, self._rounds).permutation(
                len(self._deck))
            self._order.extend(int(j) for j in perm)
            self._rounds += 1
        p, o = self._deck[self._order[i]]
        return int(p), int(o)

    def tokens(self, pass_id: int, i: int, n: int, vocab: int) -> np.ndarray:
        return seed_rng(self.seed, _TOKENS, pass_id, i).integers(
            0, vocab, n).astype(np.int32)


def sample_rng(seed: int) -> np.random.Generator:
    """Substream for choosing the requests the reference checks."""
    return seed_rng(seed, _SAMPLE)

