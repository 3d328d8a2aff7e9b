"""Mean host time of one scheduler tick that did work, in ms, over the
ticks that began in the window: ``ServeEngine.tick`` timed on the host
clock by the benchmark's wrapper around each call."""


def read(ctx):
    xs = [dt for t, dt, worked in ctx.ticks
          if worked and ctx.t_open <= t < ctx.t_close]
    return sum(xs) / len(xs) * 1e3 if xs else None
