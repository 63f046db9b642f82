#!/usr/bin/env python3
"""Two controls of a sparse-attention cell's output check that are about
WHICH positions attention reads, not about precision:

    python3 benchmarks/check_selection.py <cell> <seed> --selection recent|dense [--rehearse]

``recent``: the served program's index scores are replaced by the
positions themselves, so that each query reads the latest ``index_topk``
positions (a sliding window) instead of the ones its indexer chose.
``dense``: the entry is served with ``model.index_topk`` set to the
slot's length, so that every cached position is read, where the
configuration states a selection. Everything else is
``check_control.py --sound``: the cell's own entry at its stated
precision, the real server in this process, the sample through
``GRPCChannel``, the configuration's check module. Each must read NOT
``correct``. Exits 0 whatever the verdict (the caller reads it).
"""

from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import check_control, server_child as sc  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("cell")
    p.add_argument("seed")
    p.add_argument("--selection", choices=("recent", "dense"), required=True)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    if args.selection == "recent":
        import jax.numpy as jnp
        from triton_client_tpu.ops import sparse_index

        def by_position(scores):
            """Minus infinity where it was (after the query), else the position."""
            position = jnp.arange(scores.shape[-1], dtype=scores.dtype)
            return jnp.where(jnp.isfinite(scores), position, scores)

        step, extend = sparse_index.step_scores, sparse_index.extend_scores
        sparse_index.step_scores = lambda *a, **k: by_position(step(*a, **k))
        sparse_index.extend_scores = lambda *a, **k: by_position(extend(*a, **k))
    else:
        entry_doc = sc.entry_doc

        def dense(cfg, rehearse, precision):
            doc = entry_doc(cfg, rehearse, precision)
            doc["model"] = {**doc["model"], "index_topk": int(doc["pipeline"]["slot_len"])}
            return doc

        sc.entry_doc = dense
    return check_control.main([args.cell, args.seed, "--sound", *(["--rehearse"] if args.rehearse else [])])


if __name__ == "__main__":
    sys.exit(main())
