"""Mean device time of one program execution in the window, in ms,
from the profiler trace.  In the window the engine runs only its step
programs (chunk, chunk + K decode steps, K decode steps), so every
program execution the trace shows is one of them."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["step_count"]:
        return None
    return ctx.trace["step_device_s"] / ctx.trace["step_count"] * 1e3
