"""1 - (union of device-op intervals) / (traced span), in percent."""


def read(ctx):
    p = ctx.get("profile")
    if not p or not p["window_s"]:
        return None
    return 100.0 * (1.0 - p["busy_s"] / p["window_s"])
