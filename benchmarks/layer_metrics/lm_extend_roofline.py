"""Operations of the window's mean extend launch on the window's mean
context (``ops_bytes/<family>.count_prefill(cfg, tokens, context)``:
counters ``lm_tokens_prefill`` and ``lm_context_prefill`` over
``lm_prefill_launches``) over peak FLOP/s, over the device time such a
launch took (``lm_prefill_us_per_token`` times its tokens). The count is
not linear in the context, so the mean launch stands for the mix only
roughly; its padding and masked work are left out, so the share reads
low. A program without the context counter (the parent of the PR that
brought it) yields nothing."""

import importlib

from benchmarks import peaks

from . import lm_prefill_us_per_token
from ._sessions import delta


def read(ctx):
    us, tokens = lm_prefill_us_per_token.read(ctx), lm_prefill_us_per_token.tokens_per_launch(ctx)
    context, launches = delta(ctx, "lm_context_prefill"), delta(ctx, "lm_prefill_launches")
    if not us or not tokens or context is None or not launches:
        return None
    cfg = ctx["cfg"]
    counts = importlib.import_module(f"benchmarks.ops_bytes.{cfg['ops_bytes']}").count_prefill(cfg, tokens, context / launches)
    peak = peaks.peaks(ctx["device"]["kind"])
    least_s = max(counts["flops"] / peak["flops_per_s"][counts["flops_dtype"]], counts["bytes"] / peak["bytes_per_s"])
    return 100.0 * least_s / (us * 1e-6 * tokens)
