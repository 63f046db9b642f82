"""The least time the chip could take for the extend launches' Pallas
kernel ``lm_extend_attention`` (ops/block_attention.py: grouped-query
attention over a session's rows, the scores kept in fast memory) over
the time it took in the trace. The program holds the kernel twice: the
instance under the scan over periods runs the FULL layers
(``lm_extend_attention.<n>``), the one under the inner scan the WINDOW
layers (``lm_extend_attention_window.<n>``). ``breakdown.device_ops``
keeps the ten largest ops, so the share is taken over the kinds whose
instance is SEEN there and the layers they run alone: counting every
layer over one kind's seconds would read high. The least time is two
operations a multiply-add of the scores and of the values over the pairs
the mask lets through (a full layer: every earlier position and the
token's own; a window layer: ``ops_bytes/smallthinker._window_pairs``) at
the window's mean tokens an extend launch on the window's mean context
(``count_extend``'s ``attention`` term), over peak FLOP/s, times the
kind's layers and the traced ``..._lm_prefill`` launches. Masked pairs
inside a key block and a launch's pad tokens are work the chip does and
the count leaves out, and the mean launch understates a mix in which the
longer launches have the longer contexts: the share can only read low.
The log carries the seen instances' share of the extend launches' device
time. A program without the kernel (the parent of the PR that brought
it) yields nothing."""

import json

from benchmarks import peaks
from benchmarks.ops_bytes.smallthinker import _window_pairs

from . import lm_prefill_us_per_token
from ._sessions import delta, kind_rows

KERNEL = "lm_extend_attention"


def read(ctx):
    ops = ((ctx.get("profile") or {}).get("breakdown") or {}).get("device_ops") or []
    seen = {"window": [s for name, s in ops if name.startswith(KERNEL + "_window")]}
    seen["full"] = [s for name, s in ops if name.startswith(KERNEL) and not name.startswith(KERNEL + "_window")]
    traced, extend_s = kind_rows(ctx, "lm_prefill")
    tokens = lm_prefill_us_per_token.tokens_per_launch(ctx)
    context, launches = delta(ctx, "lm_context_prefill"), delta(ctx, "lm_prefill_launches")
    if not any(seen.values()) or not traced or not tokens or context is None or not launches:
        return None
    context /= launches
    m = ctx["cfg"]["model"]
    pairs = {"full": tokens * context + tokens * (tokens + 1) / 2,
             "window": _window_pairs(tokens, context, m["sliding_window_size"])}
    peak = peaks.peaks(ctx["device"]["kind"])["flops_per_s"]["bf16"]
    least_s = {kind: 2 * m["num_attention_heads"] * n * 2 * m["head_dim"] / peak for kind, n in pairs.items()}
    kinds = [kind for kind in seen if seen[kind]]
    took = sum(sum(seen[kind]) for kind in kinds)
    print(json.dumps({KERNEL: {"device_s": seen, "share_of_extend_launches": took / extend_s if extend_s else None,
                               "launches": traced, "tokens_a_launch": tokens, "context_a_launch": context,
                               "least_s_a_layer": least_s}}), flush=True)
    return 100.0 * traced * sum(m["layer_types"].count(kind) * least_s[kind] for kind in kinds) / took
