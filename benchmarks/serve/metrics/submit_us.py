"""Mean host time of one ``Session.submit_i`` call, in microseconds,
over the requests due in the window: the intake hand-off (the paper's
exchange latency), timed around each call by the load generator."""


def read(ctx):
    xs = [r.submit_s for r in ctx.recs if r.submit_s > 0]
    return sum(xs) / len(xs) * 1e6 if xs else None
