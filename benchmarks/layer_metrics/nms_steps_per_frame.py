"""Greedy steps the fused 2D decode+NMS kernel ran a frame over the
window: the staged channel's counters ``nms_steps`` (steps summed over
the kernel's groups of eight frames, read back with each launch's rows)
over ``nms_frames`` (frames of those launches), ``/snapshot`` ->
``channel``, after less before. ``max_det`` (300) means the kernel's
early stop never engaged, an eighth of it (37.5) that only the eight
frames a step did. A program without the counters (the parent of the PR
that brought them) yields nothing."""


def read(ctx):
    before = (ctx.get("snapshot_before") or {}).get("channel") or {}
    after = (ctx.get("snapshot_after") or {}).get("channel") or {}
    if "nms_frames" not in after:
        return None
    frames = after["nms_frames"] - before.get("nms_frames", 0)
    steps = after["nms_steps"] - before.get("nms_steps", 0)
    return steps / frames if frames else None
