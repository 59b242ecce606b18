"""wal.fsync_ms: mean ``wal.fsync`` span of the window (``serve/wal.py``,
called through ``serve/registry.py``): the group commit every
``fsync_every`` write-ahead records pay."""


def read(ctx):
    syncs = [s["t1"] - s["t0"] for s in ctx.spans if s["name"] == "wal.fsync"]
    if not syncs:
        return None
    return sum(syncs) / len(syncs) * 1e3
