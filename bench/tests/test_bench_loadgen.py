"""Traffic: same seed, same requests; any seed, the same sizes in the same
order, other prompt tokens."""
from collections import Counter

import numpy as np

from bench import loadgen
from bench.tests.tiny import ROOT


def mixes():
    return sorted((ROOT / "bench" / "traffic").glob("*.json"))


def test_deck_quantiles_follow_the_mix():
    for path in mixes():
        mix = loadgen.Mix.load(path)
        pairs = mix.pairs()
        assert len(pairs) == mix.deck
        assert pairs[:, 0].min() >= mix.prompt.min
        assert pairs[:, 0].max() <= mix.prompt.max
        assert pairs[:, 1].min() >= mix.output.min
        assert pairs[:, 1].max() <= mix.output.max


def test_same_seed_same_requests_and_any_seed_same_sizes():
    mix = loadgen.Mix.load(mixes()[0])
    big = 2**33 + 12345
    a, b = loadgen.RequestStream(mix, big), loadgen.RequestStream(mix, big)
    c = loadgen.RequestStream(mix, 7)
    n = mix.deck
    la = [a.lengths(i) for i in range(2 * n)]
    assert la == [b.lengths(i) for i in range(2 * n)]
    assert la == [c.lengths(i) for i in range(2 * n)]
    deck = Counter(map(tuple, mix.pairs().tolist()))
    for k in range(2):   # each round is one permutation of the deck
        assert Counter(la[k * n:(k + 1) * n]) == deck
    assert la[:n] != la[n:]
    assert np.array_equal(a.tokens(1, 3, 50, 1000), b.tokens(1, 3, 50, 1000))
    assert not np.array_equal(a.tokens(1, 3, 50, 1000),
                              c.tokens(1, 3, 50, 1000))
    assert not np.array_equal(a.tokens(1, 3, 50, 1000),
                              a.tokens(2, 3, 50, 1000))
