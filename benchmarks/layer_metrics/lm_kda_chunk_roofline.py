"""The least time the chip could take for the extend launches' Pallas
kernel ``lm_kda_chunk`` (ops/delta_attention.py: the chunkwise delta
rule over a head's state) over the time it took in the trace. The
program holds the kernel twice: the instance under the scan over periods
runs every KDA layer after the leading dense ones, the other the dense
layers' own. ``breakdown.device_ops`` keeps the ten largest ops, and at
the served sizes the second (a tenth of the first) is not among them: so
the share is taken of the LARGEST instance alone and of the layers it
runs (counting every KDA layer over the one instance seen would read a
tenth high). ``ops_bytes/<family>.count_kda_chunk`` a layer at the
window's mean tokens an extend launch, the larger of its operations over
peak FLOP/s and its least bytes over peak bytes/s, times those layers
and the traced ``..._lm_prefill`` launches. The log carries the seen
instances' share of the extend launches' device time. A program without
the kernel (the parent of the PR that brought it) yields nothing."""

import importlib
import json

from benchmarks import peaks

from . import lm_prefill_us_per_token
from ._sessions import kind_rows


def read(ctx):
    ops = ((ctx.get("profile") or {}).get("breakdown") or {}).get("device_ops") or []
    seen = [s for name, s in ops if name.startswith("lm_kda_chunk")]
    traced, extend_s = kind_rows(ctx, "lm_prefill")
    tokens = lm_prefill_us_per_token.tokens_per_launch(ctx)
    cfg = ctx["cfg"]
    count = getattr(importlib.import_module(f"benchmarks.ops_bytes.{cfg['ops_bytes']}"), "count_kda_chunk", None)
    if not seen or not traced or not tokens or count is None:
        return None
    counts = count(cfg, tokens)
    peak = peaks.peaks(ctx["device"]["kind"])
    least_s = max(counts["flops"] / peak["flops_per_s"][counts["flops_dtype"]], counts["bytes"] / peak["bytes_per_s"])
    model = cfg["model"]
    layers = model["layer_types"][model["first_k_dense_replace"]:].count("kda")  # those under the scan: the largest instance's
    print(json.dumps({"lm_kda_chunk": {"device_s": seen, "share_of_extend_launches": sum(seen) / extend_s if extend_s else None,
                                       "launches": traced, "tokens_a_launch": tokens, "least_s_a_layer": least_s}}), flush=True)
    return 100.0 * layers * traced * least_s / max(seen)
