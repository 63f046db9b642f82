"""Share of the bytes the staged channel placed on the device over the
window that crossed in a changed view (``channel/staged.py``:
``transfer_view``, a free view of the caller's buffer whose minor
dimensions are whole tiles): the counters ``staged_dense_bytes`` over
``staged_bytes``, ``/snapshot`` -> ``channel``, after less before, in
percent. 100 in a cell whose launches stage frame batches; 0 where every
staged array goes as it came (token ids, points). A program without the
counters (the parent of the PR that brought them) yields nothing."""


def read(ctx):
    before = (ctx.get("snapshot_before") or {}).get("channel") or {}
    after = (ctx.get("snapshot_after") or {}).get("channel") or {}
    if "staged_dense_bytes" not in after:
        return None
    staged = after["staged_bytes"] - before.get("staged_bytes", 0)
    dense = after["staged_dense_bytes"] - before.get("staged_dense_bytes", 0)
    return 100.0 * dense / staged if staged else None
