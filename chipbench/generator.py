"""The one traffic generator: reads a mix's parameters, draws requests.

A mix (``chipbench/traffic/<name>.json``) lists its kinds of request as
``[prompt_tokens, output_tokens, weight]`` and gives a ``schedule_seed``.
The loop is closed: the cell's clients each send their next request when
the last one ends.

Every run gets the same schedule.  The requests come in rounds, each
round a fixed multiset of the kinds built from the weights (largest
remainder), in an order drawn from the mix's ``schedule_seed``; client
``c``'s ``k``-th request is entry ``k * clients + c`` of the rounds laid
end to end.  The run's seed draws only the token ids.  So runs with
different seeds differ in content, not in the work or its order.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Item:
    rid: int
    prompt: np.ndarray           # int32 token ids
    output_len: int


def multiset(choices, n: int) -> list:
    """``n`` values from ``[[*value, weight], ...]`` in proportion to the
    weights, by largest remainder (ties to the earlier choice); each value
    is the entry without its weight, as a tuple."""
    vals = [tuple(int(x) for x in c[:-1]) for c in choices]
    w = np.array([float(c[-1]) for c in choices])
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    order = sorted(range(len(vals)), key=lambda i: (-(exact[i] - counts[i]), i))
    for i in order[: n - counts.sum()]:
        counts[i] += 1
    return [v for v, c in zip(vals, counts) for _ in range(c)]


class Traffic:
    """Requests of one mix for one run, handed out client by client."""

    def __init__(self, mix: dict, *, seed: int, vocab: int, clients: int):
        if clients < 1:
            raise ValueError("a closed loop needs clients >= 1")
        self.mix, self.vocab, self.clients = mix, vocab, clients
        self.seed = int(seed) % 2**63
        self.schedule = int(mix["schedule_seed"])
        self.round_size = int(mix["round"])
        self.kinds: List[tuple] = []      # (prompt, output) in schedule order
        self.items: dict = {}             # (client, k) -> Item

    def _kind(self, i: int) -> tuple:
        while len(self.kinds) <= i:
            r = len(self.kinds) // self.round_size
            rng = np.random.default_rng([self.schedule, 3, r])
            kinds = multiset(self.mix["requests"], self.round_size)
            self.kinds += [kinds[j] for j in rng.permutation(len(kinds))]
        return self.kinds[i]

    def request(self, client: int, k: int) -> Item:
        """Client ``client``'s ``k``-th request (the same object each call)."""
        key = (client, k)
        if key not in self.items:
            rid = k * self.clients + client
            prompt_len, output_len = self._kind(rid)
            prompt = np.random.default_rng([self.seed, 1, rid]).integers(
                0, self.vocab, prompt_len, dtype=np.int32)
            self.items[key] = Item(rid, prompt, output_len)
        return self.items[key]

    def lengths(self) -> List[int]:
        """Every prompt length this mix can send (the shapes to warm)."""
        return sorted({int(r[0]) for r in self.mix["requests"]})
