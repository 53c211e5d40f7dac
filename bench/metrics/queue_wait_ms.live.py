"""queue_wait_ms.live: median wait of a request from admission to the
launch of the dispatch that carries its first frame, the server's own
counter (``session.stats()["queue_wait_p50_ms"]``) over the window."""


def read(ctx):
    return ctx.stats.get("queue_wait_p50_ms") if ctx.stats["batches"] else None
