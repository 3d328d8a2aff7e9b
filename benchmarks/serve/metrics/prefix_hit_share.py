"""Share (%) of the window's prompt positions served from the prefix
cache: the engine's ``prefill_tokens_saved`` counter, from the window's
opening to the end of the run, over the bucketed (left-padded) prompt
positions of the requests due in the window."""


def _bucket(n):
    b = 8
    while b < n:
        b *= 2
    return b


def read(ctx):
    total = sum(_bucket(len(r.prompt)) for r in ctx.recs)
    if not total:
        return None
    saved = (ctx.stats_end["prefill_tokens_saved"]
             - ctx.stats_open["prefill_tokens_saved"])
    return 100.0 * saved / total
