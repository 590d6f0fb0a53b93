"""The one general request generator: a seeded stream of sessions.

A session is an optional shared prefix (a document, a system prompt)
and one or more uses of it; each use is a request whose prompt is the
prefix followed by its own suffix, with its own output length. The uses
of one session are spread over the stream, with a drawn number of other
requests between two of them, so a prefix cache sees a working set and
a time between uses. With no prefix and one use a session is a single
unshared request. Everything a mix is lives in its traffic file:

    "requests": {
      "shape_seed": n,              draws every length, count and gap
      "suffix": <dist>,             tokens of each request's own prompt
      "output": <dist>,             tokens to generate
      "prefix": <dist> | null,      tokens shared by a session's uses
      "uses":   <dist>,             requests per session
      "gap":    <dist>              other requests between two uses
    }

    <dist> is one of
      {"dist": "const",     "value": n}
      {"dist": "uniform",   "min": a, "max": b}           integers, inclusive
      {"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}
      {"dist": "geometric", "mean": m}                     0, 1, 2, ...

Two generators draw a stream. The mix's own `shape_seed` draws its
shape — every length, how many uses, every gap — so the amount of work,
what shares which prefix and when are the mix's: one fixed request list,
the same in every run. `--seed` draws the token ids (uniform over
[1, vocab); 0 is the program's padding id) and, in the drivers, the
weights. Speed depends on the shape and not on the ids: with the shape
redrawn per seed, six draws moved the doc mix's tokens per second by
7 % and its tails by 12-14 % (chip runs, PR 22, PERF.md), the draw's
noise and not the system's, where the contract allows no bound over
10 %. The price is that a claim made against one list is not tested on
another draw: a PR that claims a gain adds a copy of the mix under
another name with another `shape_seed` (a data file) and shows it there
too. A fixed order of draws makes the stream a function of (parameters,
vocab, max_len, seed, n) alone, and the first n requests of a longer
stream are the same requests.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def draw(rng: np.random.Generator, spec: dict) -> int:
    kind = spec["dist"]
    if kind == "const":
        return int(spec["value"])
    if kind == "uniform":
        return int(rng.integers(spec["min"], spec["max"] + 1))
    if kind == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"])
        return int(min(max(round(x), spec["min"]), spec["max"]))
    if kind == "geometric":
        # numpy's geometric counts trials to the first success (>= 1).
        return int(rng.geometric(1.0 / (spec["mean"] + 1.0)) - 1)
    raise ValueError(f"unknown distribution {kind!r}")


def generate(params: dict, *, vocab_size: int, max_len: int, seed: int,
             n: int) -> List[dict]:
    """The first `n` requests of the stream, in submission order. Each is
    {"rid", "session", "prefix_len", "prompt" (int32 array),
    "max_new_tokens"}; prompt + output never exceeds `max_len`, so no
    request is cut short by the cache."""
    shape = np.random.default_rng(params["shape_seed"])
    ids = np.random.default_rng(seed)
    prefix_spec: Optional[dict] = params.get("prefix")
    placed: dict = {}  # stream position -> request
    cursor = 0        # first position not yet filled
    session = 0
    while cursor < n:
        prefix = (
            ids.integers(1, vocab_size, size=draw(shape, prefix_spec),
                         dtype=np.int32)
            if prefix_spec else np.zeros(0, np.int32)
        )
        at = cursor
        for use in range(draw(shape, params["uses"])):
            if use:
                at += 1 + draw(shape, params["gap"])
            while at in placed:
                at += 1
            suffix = ids.integers(
                1, vocab_size, size=draw(shape, params["suffix"]),
                dtype=np.int32,
            )
            prompt = np.concatenate([prefix, suffix])[: max_len - 1]
            placed[at] = {
                "session": session,
                "prefix_len": int(min(prefix.size, prompt.size)),
                "prompt": prompt,
                "max_new_tokens": max(1, min(
                    draw(shape, params["output"]), max_len - prompt.size
                )),
            }
        session += 1
        while cursor in placed:
            cursor += 1
    out = []
    for rid in range(n):
        req = placed[rid]
        req["rid"] = rid
        out.append(req)
    return out
