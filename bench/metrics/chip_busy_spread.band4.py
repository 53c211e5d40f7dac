"""chip_busy_spread.band4: (max - min) of the cell's chips' device busy
time in the traced slice over their mean (%), from the reduced trace's
``busy_s_per_chip``.  A frame is done when its slowest shard is, so the
spread is time the other chips wait.  Nothing to read with no trace, no
dispatch, or fewer than two chips."""


def read(ctx):
    if ctx.trace is None or not ctx.stats["batches"]:
        return None
    busy = ctx.trace.get("busy_s_per_chip") or []
    mean = sum(busy) / len(busy) if busy else 0.0
    if len(busy) < 2 or mean <= 0:
        return None
    return 100.0 * (max(busy) - min(busy)) / mean
