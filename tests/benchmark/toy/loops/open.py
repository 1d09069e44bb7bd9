"""Arrivals that a test adds by a file and a mix alone: an open loop at the
mix's ``rate_per_s``, Poisson from the seed, each arrival a caller of its
own dealing from the mix's hands. The harness's loop asks ``due`` after
every iteration; nothing of the harness or the generator is changed."""

import numpy as np

from benchmark import traffic


class Open:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self._sizes = traffic.Client(mix, seed, 0, vocab)
        self._gaps = np.random.default_rng([int(seed), 0xA221])
        self._rate = float(mix["rate_per_s"])
        self._next_at = 0.0
        self._sent = 0

    def due(self, now_s: float, finished) -> list:
        out = []
        while self._next_at <= now_s:
            out.append((self._sent, *self._sizes.next()))
            self._sent += 1
            self._next_at += self._gaps.exponential(1.0 / self._rate)
        return out


def source(mix: dict, seed: int, vocab: int) -> Open:
    return Open(mix, seed, vocab)
