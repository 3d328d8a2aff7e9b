"""Mean time a request waited for a decode slot, in ms: the engine's
``queue_wait_ns`` counter (submit to first bind, on the engine thread)
over its ``binds``, from the window's opening to the end of the run.
None where the program keeps no such counters."""


def read(ctx):
    a, b = ctx.stats_open, ctx.stats_end
    if "binds" not in b:
        return None
    n = b["binds"] - a["binds"]
    if not n:
        return None
    return (b["queue_wait_ns"] - a["queue_wait_ns"]) / n * 1e-6
