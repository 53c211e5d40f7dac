"""host_complete_ms.live: median host time of completing one dispatch
(finalize, per-request slices, future resolution, done-callbacks), the
server's ``sr.complete`` span (``session.stats()["complete_p50_ms"]``)
over the window."""


def read(ctx):
    return ctx.stats.get("complete_p50_ms") if ctx.stats["batches"] else None
