"""The one general load generator: a traffic mix is a data file of
parameters under ``<paths>/traffic/<name>.json`` that this module reads.

Sizes. A mix holds a ``deck`` of that many (prompt length, new tokens)
cards, built from its weights by largest remainder: the same set of sizes
for every seed. The deck is cut into hands of ``hand`` cards that each
hold every new-token count once and prompt lengths from across the set.
Each client deals hand after hand; ``--seed`` and the client's number
draw the order of the hands, the order inside each, and the token ids. So
two seeds offer the same set of sizes in another order, and any stretch
of a window holds nearly the same work whatever the seed: in a closed
loop over slots that decode in lockstep the order of the sizes IS the
schedule of joins and leaves, and the driver's seeds are so many
schedules. Dealt in hands, the seeds' windows hold nearly the same requests
and their rate and mean times agree; the tails of some hundred requests
still follow the schedule, and are recorded, not judged (PERF.md, PR 23).

Arrivals. The mix's ``loop`` key names a module ``<paths>/loops/<loop>.py``
whose ``source(mix, seed, vocab)`` gives an object with one method,
``due(now_s, finished)``: the requests to submit now, as (client, prompt
token ids, max_new), given the seconds since the window opened and the
clients whose requests ended since the last call (None at the opening).
The harness asks after every loop iteration, so a closed loop answers the
finished clients and an open loop the arrivals that have come due.
"""

from __future__ import annotations

import numpy as np


def _apportion(values, weights, n: int) -> list:
    """``n`` cards holding ``values`` in the proportion of ``weights``
    (largest remainder), in the order of ``values``."""
    w = np.asarray(weights, float)
    quota = w / w.sum() * n
    count = np.floor(quota).astype(int)
    for i in np.argsort(-(quota - count), kind="stable")[: n - count.sum()]:
        count[i] += 1
    return [v for v, c in zip(values, count) for _ in range(c)]


def deck(mix: dict) -> list:
    """The mix's deck of (prompt_len, max_new) cards: the same for every
    seed and client. New-token counts are dealt round the prompt lengths
    with a stride, so every prompt length meets every count."""
    n = int(mix["deck"])
    lens = _apportion(mix["prompt_len"]["values"], mix["prompt_len"]["weights"], n)
    news = _apportion(mix["max_new"]["values"], mix["max_new"]["weights"], n)
    k = len(mix["max_new"]["values"])
    order = sorted(range(n), key=lambda i: (i % k, i))
    news = [news[j] for j in np.argsort(order, kind="stable")]
    return list(zip(lens, news))


def hands(mix: dict) -> list:
    """The deck cut into hands of ``hand`` cards, every ``deck / hand``-th
    card to a hand: with ``hand`` the number of new-token counts, each hand
    holds every count once and prompt lengths from across the deck."""
    cards, size = deck(mix), int(mix["hand"])
    if size < 1 or len(cards) % size:
        raise ValueError(f"a deck of {len(cards)} cards does not cut into hands of {size}")
    n = len(cards) // size
    return [cards[j::n] for j in range(n)]


def prompt_lengths(mix: dict) -> list:
    """Every prompt length the mix can send: what set-up has to warm."""
    return sorted({int(v) for v in mix["prompt_len"]["values"]})


class Client:
    """One caller's endless sequence of requests: the mix's sizes, their
    order and the token ids from the seed and the client's number."""

    def __init__(self, mix: dict, seed: int, index: int, vocab: int):
        self._hands = hands(mix)
        self._order = np.random.default_rng([int(seed), int(index), 0x0DE])
        self._rng = np.random.default_rng([int(seed), int(index), 0x7DA])
        self._vocab = int(vocab)
        self._dealt: list = []
        self.index = index

    def next(self) -> tuple[list, int]:
        """(prompt token ids, max_new) of this client's next request."""
        if not self._dealt:
            for j in self._order.permutation(len(self._hands)):
                hand = self._hands[j]
                self._dealt += [hand[i] for i in self._order.permutation(len(hand))]
            self._dealt.reverse()
        p_len, max_new = self._dealt.pop()
        prompt = self._rng.integers(0, self._vocab, size=int(p_len)).tolist()
        return prompt, int(max_new)


def check(mix: dict) -> None:
    if mix.get("sampling") != "greedy":
        raise ValueError("only greedy traffic can be held to the reference")
    hands(mix)
