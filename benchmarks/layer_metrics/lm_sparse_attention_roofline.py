"""The least time the chip could take for the extend launches' Pallas
kernel ``lm_sparse_attention`` over the time its instances took in the
trace (``breakdown.device_ops``: the ten largest ops; the kernel's
instance in the layer scan and the one in the leading dense layer are
both among them at the served sizes, and one that is not makes the share
read low). ``ops_bytes/<family>.count_selected_kernel`` a layer at the
window's mean tokens and mean context an extend launch, times the layers
and the traced ``..._lm_prefill`` launches. The kernel multiplies every
pair of a key block under the mask and the count holds the selected
pairs only, so the share reads low as the context grows. A program
without the kernel or the context counter yields nothing."""

import importlib

from benchmarks import peaks

from . import lm_prefill_us_per_token
from ._sessions import delta, kind_rows


def read(ctx):
    ops = ((ctx.get("profile") or {}).get("breakdown") or {}).get("device_ops") or []
    seconds = sum(s for name, s in ops if name.startswith("lm_sparse_attention"))
    traced, _ = kind_rows(ctx, "lm_prefill")
    tokens = lm_prefill_us_per_token.tokens_per_launch(ctx)
    context, launches = delta(ctx, "lm_context_prefill"), delta(ctx, "lm_prefill_launches")
    cfg = ctx["cfg"]
    count = getattr(importlib.import_module(f"benchmarks.ops_bytes.{cfg['ops_bytes']}"), "count_selected_kernel", None)
    if not seconds or not traced or not tokens or context is None or not launches or count is None:
        return None
    counts = count(cfg, tokens, context / launches)
    peak = peaks.peaks(ctx["device"]["kind"])
    least_s = max(counts["flops"] / peak["flops_per_s"][counts["flops_dtype"]], counts["bytes"] / peak["bytes_per_s"])
    return 100.0 * cfg["model"]["num_hidden_layers"] * traced * least_s / seconds
