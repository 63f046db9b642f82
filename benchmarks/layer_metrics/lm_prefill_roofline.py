"""Operations of the window's mean prefill launch
(``ops_bytes/<family>.count_prefill``, a prompt on an empty cache) over
peak FLOP/s, over the device time such a launch took
(``lm_prefill_us_per_token`` times its tokens)."""

import importlib

from benchmarks import peaks

from . import lm_prefill_us_per_token


def read(ctx):
    us, tokens = lm_prefill_us_per_token.read(ctx), lm_prefill_us_per_token.tokens_per_launch(ctx)
    if not us or not tokens:
        return None
    cfg = ctx["cfg"]
    counts = importlib.import_module(f"benchmarks.ops_bytes.{cfg['ops_bytes']}").count_prefill(cfg, tokens)
    peak = peaks.peaks(ctx["device"]["kind"])
    least_s = max(counts["flops"] / peak["flops_per_s"][counts["flops_dtype"]], counts["bytes"] / peak["bytes_per_s"])
    return 100.0 * least_s / (us * 1e-6 * tokens)
