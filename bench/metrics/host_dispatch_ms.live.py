"""host_dispatch_ms.live: mean host time of one launch (assembly done,
executor call until it returns), the session's own span, over the window."""


def read(ctx):
    return ctx.stats["dispatch_mean_ms"] if ctx.stats["batches"] else None
