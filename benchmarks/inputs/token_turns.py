"""Seeded token streams for a ``sessions`` mix whose sessions are fed in
several many-token TURNS before they are stepped.

A stream is a first turn of ``first_turn`` tokens, then ``k`` turns of
``turn`` tokens each, ``k`` from ``turns`` in equal shares (``n``
streams hold ``n / len(turns)`` of each, in an order the seed draws),
then ``steps`` requests of ONE token each: every request is appended to
what the session's cache holds. The ids are uniform over the vocabulary
the configuration holds (``model.vocab_size``). A stream is drawn whole
from the seed and teacher-forced: no request depends on an answer. Each
request states the items it completes: the tokens it carries."""

from __future__ import annotations

import numpy as np


def make(rng: np.random.Generator, n: int, params: dict, cfg: dict) -> list[list[dict]]:
    first, turn, steps = int(params["first_turn"]), int(params["turn"]), int(params["steps"])
    ladder = [int(k) for k in params["turns"]]
    vocab = int(cfg["model"]["vocab_size"])
    streams = []
    for k in rng.permutation([ladder[i % len(ladder)] for i in range(n)]):
        sizes = [first, *[turn] * int(k), *[1] * steps]
        ids = rng.integers(0, vocab, sum(sizes), dtype=np.int32)
        ends = np.cumsum(sizes)
        streams.append([{"tokens": ids[None, end - size : end], "items": int(size)} for size, end in zip(sizes, ends)])
    return streams
