"""The one generator of serving requests.  A traffic file gives, for the
prompt length and for `max_tokens`, a list of [value, count] pairs: two
multisets (6:6:5:3 prompt lengths are 20 values, 3:3:3:1 output lengths
10).  A deck is as long as the least common multiple of the two sizes (20)
and holds each multiset as often as fits.  The pairing is part of the mix
and the same for every seed: prompt lengths in rising order against output
lengths dealt round-robin (32, 64, 128, 256, 32, 64, ...).  A seed only
shuffles the order of a deck's requests (another shuffle for each deck of a
run).  So every seed does the same work in another order - the time a
request stays in the batch follows its output length, so a pairing that
changed with the seed would change the mean context length, and with it the
decode step.  Token ids are uniform over the vocabulary, from the seed and
the request's number, so no two prompts share a prefix."""
import math
import random

import numpy as np


def _multiset(pairs):
    out = []
    for value, count in pairs:
        out.extend([int(value)] * int(count))
    return out


def _round_robin(pairs):
    """The multiset of [value, count] pairs, one of each value in turn
    while its count lasts: [[1, 2], [5, 1]] -> [1, 5, 1]."""
    left = [[int(v), int(c)] for v, c in pairs]
    out = []
    while any(c for _, c in left):
        for item in left:
            if item[1]:
                out.append(item[0])
                item[1] -= 1
    return out


class Requests:
    """request(i) -> (prompt ids, max_tokens), the same for the same seed."""

    def __init__(self, traffic, vocab_size, seed):
        self.vocab, self.seed = int(vocab_size), seed
        lens = sorted(_multiset(traffic["prompt_len"]))
        outs = _round_robin(traffic["max_tokens"])
        self.deck_size = math.lcm(len(lens), len(outs))
        self.pairs = list(zip(lens * (self.deck_size // len(lens)),
                              outs * (self.deck_size // len(outs))))
        self.pairs.sort()
        self._decks = {}

    def shape(self, index):
        """(prompt length, max_tokens) of the index-th request."""
        cycle, pos = divmod(index, self.deck_size)
        if cycle not in self._decks:
            deck = list(self.pairs)
            random.Random(self.seed * 1000003 + cycle).shuffle(deck)
            self._decks[cycle] = deck
        return self._decks[cycle][pos]

    def request(self, index):
        plen, max_tokens = self.shape(index)
        rng = np.random.default_rng([self.seed, index])
        return rng.integers(0, self.vocab, plen).tolist(), max_tokens

    def prompt_lengths(self):
        return sorted({p for p, _ in self.pairs})
