"""Closed loop, one deal for every seed: ``clients`` callers, each sending
its next request the moment its last one ended, all taking their cards
from ONE endless sequence that no seed reorders. ``--seed`` draws the token
ids (and, in the build file, the weights) and nothing else.

For a mix whose window holds only a few dozen requests that differ widely
in output tokens for the work they cost. ``loops/closed.py`` lets every
client deal the hands in an order of its own drawn from the seed, which
serves a mix well when a window holds a hundred requests or more. Where it
holds some 36 of them and a (16384-token prompt, 32 new tokens) card yields a
sixteenth of the tokens a (4096, 128) card does for its prefill work, the
drawn order decides what a window holds: six seeds spread
``out_tokens_per_s`` by 15.5 % against a bound of 2.5 % (v5e, PR 28's first
round; PERF.md section 6). Here every seed's window holds the same requests
in the same order of joins and leaves, so two runs differ by the machine
and by what the ids route where, and a change is judged on one schedule
that holds the whole deck's mix.

The sequence: the deck's hands (``traffic.hands``: every new-token count
once a hand, prompt lengths from across the deck) one after another, round
and round, the cards of hand ``h`` turned by ``h`` places so that the long
prompts of neighbouring hands do not arrive together. The mix's
``first_card`` (0 unless given) says at which card of the round the deal
starts. It is there because a request that ends a tenth of a second before
the window closes sends its client's next one inside the window, and one
that ends a tenth after does not: with some 36 requests a window that one
request, served in the drain at a fifth of the others' time a token, moves
``tpot_mean_ms`` by 3 %, and a run that is 0.3 % slower flips it. A mix
sets ``first_card`` so that at the speed it was measured at no request ends
near its window's close, and says so beside the key.
"""

import numpy as np

from benchmark import traffic


class OneDeal:
    def __init__(self, mix: dict, seed: int, vocab: int):
        hands = traffic.hands(mix)
        self._cards = [hand[(i + h) % len(hand)]
                       for h, hand in enumerate(hands) for i in range(len(hand))]
        self._first = int(mix.get("first_card", 0))
        self._clients = int(mix["clients"])
        self._seed, self._vocab = int(seed), int(vocab)
        self._dealt = 0

    def _next(self) -> tuple[list, int]:
        p_len, max_new = self._cards[(self._first + self._dealt) % len(self._cards)]
        ids = np.random.default_rng([self._seed, self._dealt, 0x7DA])
        self._dealt += 1
        return ids.integers(0, self._vocab, size=int(p_len)).tolist(), int(max_new)

    def due(self, now_s: float, finished) -> list:
        who = range(self._clients) if finished is None else finished
        return [(i, *self._next()) for i in who]


def source(mix: dict, seed: int, vocab: int) -> OneDeal:
    return OneDeal(mix, seed, vocab)
