"""Seeded token STREAMS for a ``sessions`` mix over a language model.

A stream is one prompt of ``P`` tokens, ``P`` from ``prompt_ladder`` in
equal shares (``n`` streams hold ``n / len(ladder)`` of each, in an
order the seed draws), then ``steps`` requests of ONE token each. The
ids are uniform over the vocabulary the configuration holds
(``model.vocab_size``: a sliced vocabulary is a smaller vocabulary). A
stream is drawn whole from the seed and teacher-forced: no request
depends on an answer (with seeded weights the largest logit changes on
rounding). Each request states the items it completes: the tokens it
carries."""

from __future__ import annotations

import numpy as np


def make(rng: np.random.Generator, n: int, params: dict, cfg: dict) -> list[list[dict]]:
    ladder = [int(p) for p in params["prompt_ladder"]]
    steps, vocab = int(params["steps"]), int(cfg["model"]["vocab_size"])
    lengths = rng.permutation([ladder[i % len(ladder)] for i in range(n)])
    streams = []
    for prompt in lengths:
        ids = rng.integers(0, vocab, int(prompt) + steps, dtype=np.int32)
        stream = [{"tokens": ids[None, :prompt], "items": int(prompt)}]
        stream += [{"tokens": ids[None, prompt + k : prompt + k + 1], "items": 1} for k in range(steps)]
        streams.append(stream)
    return streams
