"""Operations a dense decoder's forward pass needs, from shapes alone.

A multiply-add counts as two operations.  Per token and layer: the
q/k/v/o projections, the SwiGLU MLP, and attention over the token's
true context (scores and the weighted sum).  Logits count only where a
token is taken from them.  Padding is not work: it is left out.
"""
from __future__ import annotations

from typing import Dict


def per_layer_token(dims: Dict[str, int], context: int) -> int:
    """One token through one layer, attending to ``context`` keys (its
    own included)."""
    d, nh, nkv, hd, f = (dims[k] for k in ("d", "nh", "nkv", "hd", "f"))
    proj = 2 * d * (nh + 2 * nkv) * hd + 2 * nh * hd * d
    mlp = 2 * 3 * d * f
    attn = 2 * 2 * nh * hd * context
    return proj + mlp + attn


def logits_row(dims: Dict[str, int]) -> int:
    return 2 * dims["d"] * dims["V"]


def prefill(dims: Dict[str, int], prompt: int) -> int:
    """A prompt of ``prompt`` true tokens (position i attends to i + 1
    keys), and the logits of the first output token."""
    keys = prompt * (prompt + 1) // 2
    fixed = per_layer_token(dims, 0)
    return (dims["L"] * (prompt * fixed
                         + 2 * 2 * dims["nh"] * dims["hd"] * keys)
            + logits_row(dims))


def decode_token(dims: Dict[str, int], context: int) -> int:
    """One output token after the first: its predecessor is fed at
    position ``context - 1`` and attends to ``context`` keys."""
    return dims["L"] * per_layer_token(dims, context) + logits_row(dims)


def request(dims: Dict[str, int], prompt: int, outputs: int) -> int:
    """Everything one request of ``prompt`` tokens and ``outputs``
    output tokens needs."""
    return prefill(dims, prompt) + sum(decode_token(dims, prompt + j)
                                       for j in range(1, outputs))
