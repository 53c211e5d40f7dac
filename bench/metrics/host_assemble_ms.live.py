"""host_assemble_ms.live: median host time of assembling one dispatch
(staging copy and ``device_put``), the server's ``sr.assemble`` span
(``session.stats()["assemble_p50_ms"]``) over the window."""


def read(ctx):
    return ctx.stats.get("assemble_p50_ms") if ctx.stats["batches"] else None
