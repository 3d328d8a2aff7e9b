"""The comparison that decides ``correct``.

Once the window has closed, a sample of the finished requests, drawn
from the seed and holding the longest of them, is run through the
plain reference: each prompt, as the engine prefilled it, followed by
the tokens the client received.  The number compared is the widest gap
by which a served token's logit lies below the reference's best logit
at that position (greedy decoding: a correct program serves the
reference's argmax, up to the rounding of its own precision).

Besides that gap, every request due in the window has to have come
back whole: its full count of tokens, and a stream that agrees with its
terminal answer.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from servebench.traffic import bucket, rng_for

# The widest gap is an extreme value: over a few hundred tokens the
# float8 control's reading ranged 0.59 to 10.4 logits on the chip, so
# more tokens are compared to keep it clear of the program's (PR 12).
SAMPLE_TOKENS = 1536        # served tokens to compare, at least ...
SAMPLE_MAX = 64             # ... unless this many requests come first


def sample(recs: List, seed: int, tokens: int = SAMPLE_TOKENS,
           most: int = SAMPLE_MAX) -> List:
    """Finished requests to compare: the longest (prompt + output) and
    then others in an order drawn from the seed."""
    done = [r for r in recs if r.ok]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: (len(done[i].prompt) + done[i].max_tokens, -i))
    order = [longest] + [int(i) for i in
                         rng_for(seed, 3).permutation(len(done))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= tokens or len(out) >= most:
            break
        out.append(done[i])
        n += done[i].max_tokens
    return out


def padded_prompt(prompt: np.ndarray) -> np.ndarray:
    """The stream the engine prefills: the prompt left-padded with 0 to
    its bucket.  The padding is input to the model like any token."""
    p = np.zeros((bucket(len(prompt)),), np.int32)
    p[len(p) - len(prompt):] = prompt
    return p


def sequence(rec) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(tokens fed, rows whose next-token logits matter, served tokens)."""
    pp = padded_prompt(rec.prompt)
    out = np.asarray(rec.out(), np.int32)
    seq = np.concatenate([pp, out[:-1]])
    rows = np.arange(len(pp) - 1, len(pp) - 1 + len(out))
    return seq, rows, out


def gaps(family, conf: Dict, params, recs: List,
         control: bool = False) -> Dict[str, float]:
    """Widest logit gap of the served tokens against the float32
    reference; with ``control``, also the widest gap of the tokens the
    float8 control would put first at the same positions.  The
    arithmetic is on the host: its shapes vary from request to request,
    and on the device each would compile anew."""
    served, ctrl, n_tok = 0.0, 0.0, 0
    for rec in recs:
        seq, rows, out = sequence(rec)
        ref = family.logits_at(conf, params, seq, rows)
        at = np.arange(len(out))
        best = ref.max(axis=-1)
        served = max(served, float(np.max(best - ref[at, out])))
        n_tok += len(out)
        if control:
            low = family.logits_at(conf, params, seq, rows, mode="fp8")
            alt = ref[at, low.argmax(axis=-1)]
            ctrl = max(ctrl, float(np.max(best - alt)))
    res = {"logit_gap": served, "tokens": n_tok, "requests": len(recs)}
    if control:
        res["control_gap"] = ctrl
    return res


def judge(checks: Dict[str, Dict]) -> bool:
    """Every number within its limit; a number with no limit yet fails."""
    return all(c["limit"] is not None and c["value"] is not None
               and c["value"] <= c["limit"] for c in checks.values())


def build_checks(gap: Optional[float], limits: Dict, missing: int,
                 wrong: int) -> Dict[str, Dict]:
    """The numbers compared, each with its limit, in print order."""
    lim = limits.get("logit_gap", {}).get("limit")
    return {
        "requests_missing": {"value": missing, "limit": 0},
        "streams_wrong": {"value": wrong, "limit": 0},
        "logit_gap": {"value": gap, "limit": lim},
    }
