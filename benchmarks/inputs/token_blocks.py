"""Seeded token streams for a ``sessions`` mix whose replies are generated
by DIFFUSION OVER BLOCKS (``model.block_length`` = B positions a block).

A stream is a prompt of ``len`` tokens, ``len`` from ``prompts`` in equal
shares (``n`` streams hold ``n / len(prompts)`` of each, in an order the
seed draws), then ``blocks`` blocks of the reply. The prompt's first
``floor(len / B) * B`` tokens are ONE extend request; its last ``len mod
B`` ride in the first block as positions already revealed. Each block is
three requests ``tokens [1, B]`` beside ``commit [1, 1]``: pass 1 (every
unrevealed position holds the ``[MASK]`` id, commit 0), pass 2 (half of
them revealed, rounded up, which ones the seed draws; commit 0) and the
commit (all revealed, commit 1): SDAR's static low-confidence schedule
at two passes a block, teacher-forced. The ids are uniform over the
vocabulary the configuration holds but its last row, which stands for
``[MASK]``. A stream is drawn whole from the seed: no request depends on
an answer. Each request states the items it completes: an extend the
tokens it carries, a denoising pass 0, a commit B."""

from __future__ import annotations

import numpy as np


def make(rng: np.random.Generator, n: int, params: dict, cfg: dict) -> list[list[dict]]:
    ladder, blocks = [int(p) for p in params["prompts"]], int(params["blocks"])
    b, mask = int(cfg["model"]["block_length"]), int(cfg["model"]["vocab_size"]) - 1
    flag = lambda value: np.full((1, 1), value, np.int32)
    streams = []
    for length in rng.permutation([ladder[i % len(ladder)] for i in range(n)]):
        fed = int(length) // b * b
        ids = rng.integers(0, mask, fed + blocks * b, dtype=np.int32)
        stream = [{"tokens": ids[None, :fed], "items": fed}]
        for k in range(blocks):
            final = ids[fed + k * b : fed + (k + 1) * b]
            hidden = np.arange(int(length) - fed if k == 0 else 0, b)  # the positions this block still has to reveal
            first = final.copy()
            first[hidden] = mask
            second = first.copy()
            shown = rng.permutation(hidden)[: -(-len(hidden) // 2)]
            second[shown] = final[shown]
            stream += [{"tokens": first[None], "commit": flag(0), "items": 0},
                       {"tokens": second[None], "commit": flag(0), "items": 0},
                       {"tokens": final[None], "commit": flag(1), "items": b}]
        streams.append(stream)
    return streams
