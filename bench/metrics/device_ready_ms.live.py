"""device_ready_ms.live: median time from the return of a dispatch's
executor call to its result being ready on the host's wait, the server's
counter (``session.stats()["device_ready_p50_ms"]``) over the window."""


def read(ctx):
    return ctx.stats.get("device_ready_p50_ms") if ctx.stats["batches"] else None
