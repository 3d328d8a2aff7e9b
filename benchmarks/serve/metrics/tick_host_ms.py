"""Mean host time of one tick that dispatched, in ms: the engine's
``tick_host_ns`` counter (the tick less its wait on the device,
``serve.sync``; the device has nothing queued meanwhile) over its
``ticks_worked``, from the window's opening to the end of the run.
None where the program keeps no such counters."""


def read(ctx):
    a, b = ctx.stats_open, ctx.stats_end
    if "ticks_worked" not in b:
        return None
    n = b["ticks_worked"] - a["ticks_worked"]
    if not n:
        return None
    return (b["tick_host_ns"] - a["tick_host_ns"]) / n * 1e-6
