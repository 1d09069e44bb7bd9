"""Closed loop: ``clients`` callers, each sending its next request the
moment its last one ended. What a replica sees from a router that holds
its in-flight count fixed, and what an offline batch is."""

from benchmark import traffic


class Closed:
    def __init__(self, mix: dict, seed: int, vocab: int):
        self.clients = [traffic.Client(mix, seed, i, vocab)
                        for i in range(int(mix["clients"]))]

    def due(self, now_s: float, finished) -> list:
        who = range(len(self.clients)) if finished is None else finished
        return [(i, *self.clients[i].next()) for i in who]


def source(mix: dict, seed: int, vocab: int) -> Closed:
    return Closed(mix, seed, vocab)
