"""shard_put_ms.band4: median host time of putting one assembled slab
straight into the band-sharded executor's input sharding, each chip
receiving its own rows, the server's ``sr.shard_put`` span
(``session.stats()["shard_put_p50_ms"]``) over the window.  A server
without that span reports nothing."""


def read(ctx):
    return ctx.stats.get("shard_put_p50_ms") if ctx.stats["batches"] else None
