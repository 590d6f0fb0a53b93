"""The request generator: a function of (parameters, seed, n) alone, inside
every clip, with the sharing structure the doc_qa mix states."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.generators import sessions  # noqa: E402

VOCAB, MAX_LEN = 50257, 1024


def mix(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)["requests"]


def stream(name, seed, n):
    return sessions.generate(
        mix(name), vocab_size=VOCAB, max_len=MAX_LEN, seed=seed, n=n
    )


def as_bytes(requests):
    return b"".join(
        r["prompt"].tobytes() + bytes([r["max_new_tokens"] % 256])
        for r in requests
    )


@pytest.mark.parametrize("name", ["chat_drain", "doc_qa_drain"])
def test_same_seed_same_bytes_other_seed_other_bytes(name):
    a, b, c = stream(name, 7, 200), stream(name, 7, 200), stream(name, 8, 200)
    assert as_bytes(a) == as_bytes(b)
    assert as_bytes(a) != as_bytes(c)
    assert [r["rid"] for r in a] == list(range(200))


@pytest.mark.parametrize("name", ["chat_drain", "doc_qa_drain"])
def test_the_seed_draws_the_ids_and_the_mix_draws_its_own_shape(name):
    """Every run of a cell does the same amount of work and shares the
    same prefixes; only the token ids (and the weights) follow --seed."""
    a, c = stream(name, 7, 300), stream(name, 8, 300)
    shape = lambda rs: [(r["prompt"].size, r["max_new_tokens"], r["session"],
                         r["prefix_len"]) for r in rs]
    assert shape(a) == shape(c)
    assert all(not np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, c))
    other = dict(mix(name), shape_seed=mix(name)["shape_seed"] + 1)
    d = sessions.generate(other, vocab_size=VOCAB, max_len=MAX_LEN, seed=7, n=300)
    assert shape(d) != shape(a)


@pytest.mark.parametrize("name", ["chat_drain", "doc_qa_drain"])
def test_a_longer_stream_begins_with_the_shorter_one(name):
    """The warm-up drain takes the head of the stream and the measured
    drain what follows, whatever its size turns out to be."""
    short, long = stream(name, 3, 64), stream(name, 3, 400)
    assert as_bytes(short) == as_bytes(long[:64])


def test_chat_respects_every_clip_and_shares_nothing():
    reqs = stream("chat_drain", 11, 2000)
    plen = np.array([r["prompt"].size for r in reqs])
    out = np.array([r["max_new_tokens"] for r in reqs])
    assert plen.min() >= 8 and plen.max() <= 384
    assert out.min() >= 16 and out.max() <= 320
    assert all(r["prefix_len"] == 0 for r in reqs)
    assert len({r["session"] for r in reqs}) == len(reqs)
    # lognormal medians as stated in the traffic file (64 in, 96 out)
    assert 56 <= np.median(plen) <= 72
    assert 88 <= np.median(out) <= 104
    assert 105 <= out.mean() <= 120
    for r in reqs:
        assert r["prompt"].dtype == np.int32
        assert r["prompt"].min() >= 1 and r["prompt"].max() < VOCAB


def test_doc_qa_has_the_stated_sharing_structure():
    reqs = stream("doc_qa_drain", 5, 3000)
    by_session = {}
    for r in reqs:
        by_session.setdefault(r["session"], []).append(r)
    # sessions cut by the end of the stream are not whole: judge the rest
    last_whole = min(r["session"] for r in reqs[-200:])
    whole = {s: rs for s, rs in by_session.items() if s < last_whole}
    assert len(whole) > 300
    gaps = []
    for rs in whole.values():
        assert 3 <= len(rs) <= 5
        doc = rs[0]["prefix_len"]
        assert 512 <= doc <= 832
        for r in rs:
            assert r["prefix_len"] == doc
            assert np.array_equal(r["prompt"][:doc], rs[0]["prompt"][:doc])
            assert 16 <= r["prompt"].size - doc <= 64
            assert 8 <= r["max_new_tokens"] <= 32
        # each ask has its own question
        assert len({r["prompt"][doc:].tobytes() for r in rs}) == len(rs)
        gaps += [b["rid"] - a["rid"] - 1 for a, b in zip(rs, rs[1:])]
    # a geometric number of other requests (mean 12) between two uses;
    # collisions with placed requests can only push a use later
    assert 11.0 <= np.mean(gaps) <= 15.0
    assert min(gaps) >= 0
    # distinct documents share nothing
    docs = {rs[0]["prompt"][:64].tobytes() for rs in whole.values()}
    assert len(docs) == len(whole)


def test_prompt_and_output_never_exceed_the_cache():
    params = {
        "shape_seed": 0,
        "prefix": {"dist": "const", "value": 120},
        "uses": {"dist": "const", "value": 2},
        "gap": {"dist": "const", "value": 1},
        "suffix": {"dist": "uniform", "min": 1, "max": 40},
        "output": {"dist": "const", "value": 50},
    }
    reqs = sessions.generate(params, vocab_size=100, max_len=128, seed=0, n=50)
    for r in reqs:
        assert 1 <= r["prompt"].size <= 127
        assert r["max_new_tokens"] >= 1
        assert r["prompt"].size + r["max_new_tokens"] <= 128


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError, match="unknown distribution"):
        sessions.draw(np.random.default_rng(0), {"dist": "zipf"})


@pytest.mark.parametrize("spec,lo,hi", [
    ({"dist": "const", "value": 5}, 5, 5),
    ({"dist": "uniform", "min": 3, "max": 5}, 3, 5),
    ({"dist": "lognormal", "median": 64, "sigma": 0.8, "min": 8, "max": 384}, 8, 384),
    ({"dist": "geometric", "mean": 12}, 0, 10 ** 6),
])
def test_draws_stay_in_range(spec, lo, hi):
    rng = np.random.default_rng(1)
    xs = [sessions.draw(rng, spec) for _ in range(2000)]
    assert min(xs) >= lo and max(xs) <= hi
    if spec["dist"] == "geometric":
        assert 11.0 <= np.mean(xs) <= 13.0
    if spec["dist"] == "uniform":
        assert set(xs) == {3, 4, 5}
