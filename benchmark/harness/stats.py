"""Metric arithmetic over what the program records. Plain functions on
plain values, so the tests feed them canned records."""

from __future__ import annotations

import math
from typing import Iterable, List, Sequence

# A percentile is reported only with ten samples beyond it: p90 needs
# 100 samples, which is also what a serving cell's drain must finish.
MIN_TAIL_SAMPLES = 10


def percentile(xs: Sequence[float], q: float) -> float:
    """Linear interpolation between order statistics (numpy's default
    rule), q in [0, 100]."""
    if not xs:
        raise ValueError("percentile of no samples")
    s = sorted(xs)
    pos = (len(s) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie beyond the q-th percentile."""
    return int(math.floor(n * (100.0 - q) / 100.0 + 1e-9))


def tpot_ms(finished: Iterable[dict]) -> List[float]:
    """Time per output token of each finished request, in ms: from its
    first token to its last, over the tokens after the first. Requests
    with fewer than two tokens have no gap and are left out."""
    out = []
    for f in finished:
        n = f["n_tokens"]
        if n >= 2:
            out.append(1e3 * (f["total_s"] - f["prefill_s"]) / (n - 1))
    return out


def saturated(finished: Sequence[dict]) -> dict:
    """The part of a drain in which the queue was never empty: from its
    start to the first token of the request that got one last (every
    request is handed over at the start, so until then one was always
    waiting). Returns its length, the tokens emitted in it and the
    requests that got their first token per second of it. A request's
    tokens after the first are taken as evenly spaced from its first
    token to its last — the decode step advances every slot together,
    so they are. What follows that moment is the drain-out: slots empty
    one by one and nothing refills them, which says how the drain ends,
    not what the system sustains."""
    end = max(f["prefill_s"] for f in finished)
    tokens = 0.0
    for f in finished:
        first, last, n = f["prefill_s"], f["total_s"], f["n_tokens"]
        if n == 1 or last <= end:
            tokens += n
        else:
            tokens += 1 + (n - 1) * (end - first) / (last - first)
    return {"seconds": end, "tokens": tokens,
            "requests_per_s": len(finished) / end}


def spread(xs: Sequence[float]) -> float:
    """Distance between the quartiles over the median: how the driver
    judges a set of runs."""
    med = percentile(xs, 50)
    return (percentile(xs, 75) - percentile(xs, 25)) / med


def span_seconds(spans, name: str) -> float:
    """Total duration of the host spans called `name`."""
    return sum(s.dur for s in spans if s.name == name)


def untraced(epochs: Sequence[dict]) -> List[dict]:
    """The epochs that count toward rates: not the one slowed by the
    device trace."""
    return [e for e in epochs if not e["traced"]]
