"""Share (%) of the chunk program's prompt positions that carry a prompt
token: the engine's ``chunk_tokens`` counter (chunk positions that are
not the bucket's left padding) over ``max_batch`` rows of
``chunk_tokens`` positions in each of its ``prefill_dispatches``, from
the window's opening to the end of the run.  None where the program
keeps no such counter."""


def read(ctx):
    a, b = ctx.stats_open, ctx.stats_end
    if "chunk_tokens" not in b:
        return None
    n = b["prefill_dispatches"] - a["prefill_dispatches"]
    if not n:
        return None
    s = ctx.cell.config["serving"]
    return (100.0 * (b["chunk_tokens"] - a["chunk_tokens"])
            / (s["max_batch"] * s["chunk_tokens"] * n))
