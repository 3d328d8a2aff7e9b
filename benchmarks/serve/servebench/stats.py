"""Arithmetic of the end-to-end metrics, over every sample of a run."""
from __future__ import annotations

from typing import Iterable, List, Optional, Sequence

import numpy as np


def percentile(samples: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0-100) of all samples, interpolated
    linearly between order statistics; None without samples."""
    xs = np.asarray(list(samples), np.float64)
    if xs.size == 0:
        return None
    return float(np.percentile(xs, q))


def ttft_samples(due: Sequence[float], first: Sequence[float]) -> List[float]:
    """Time from when each request was due to its first token at the
    client, in seconds: a request held back by a stall counts the
    stall."""
    return [f - d for d, f in zip(due, first)]


def gaps(stamps: Sequence[float]) -> List[float]:
    """Gaps between consecutive tokens of one request, as the client
    saw them."""
    return [b - a for a, b in zip(stamps[:-1], stamps[1:])]


def rate(stamps: Iterable[float], t0: float, t1: float) -> float:
    """Events with a stamp in ``[t0, t1)``, divided by ``t1 - t0``: all
    the work over all the time."""
    n = sum(1 for t in stamps if t0 <= t < t1)
    return n / (t1 - t0)
