"""Device time of the model's launcher programs over their launches,
from the profiler trace: the channel names them ``jit_mdl_<model>_<version>``."""


def launcher_rows(ctx):
    launches = (ctx.get("profile") or {}).get("launches", {})
    return [v for k, v in launches.items() if f"mdl_{ctx['model']}_" in k]


def read(ctx):
    rows = launcher_rows(ctx)
    count = sum(r["count"] for r in rows)
    return 1e3 * sum(r["device_s"] for r in rows) / count if count else None
