"""The whole model step's share (%) of the chip's bf16 peak: the
operations the forward pass needs for the work that reached the client
in the window (prompt tokens without padding, output tokens, attention
over each token's true context, logits only where a token is taken;
``servebench/flops.py``), over window seconds times the peak."""


def read(ctx):
    if ctx.window_flops <= 0:
        return None
    return 100.0 * ctx.window_flops / (ctx.seconds * ctx.peak["bf16_flops"])
