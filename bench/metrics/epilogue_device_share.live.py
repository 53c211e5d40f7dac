"""epilogue_device_share.live: share (%) of the traced slice's device busy
time in which an operation of the ``sr_epilogue`` scope runs (anchor,
pixel shuffle, clip, cast): the union of their intervals over the union
of all operations', read from the trace the run reduced
(``bench/trace_program.py``)."""

from bench import trace_program


def read(ctx):
    if ctx.trace is None:
        return None
    trace = trace_program.load_for(ctx.trace)
    if trace is None:
        return None
    r = trace_program.reduce(trace, ctx.chips)
    if "sr_epilogue" not in r["scopes"] or r["busy_s"] <= 0:
        return None
    return 100.0 * r["scopes"]["sr_epilogue"] / r["busy_s"]
