"""The eight per-layer metrics of one engine iteration (ISSUE 24): each
reader on a canned record, on a record of a program without the spans
and counters (a parent commit), and in the line of a `--rehearsal
--trace 1` run of each serving cell."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import manifest  # noqa: E402
from benchmark.harness.trace_reduce import NO_SPAN, Event  # noqa: E402

# metric -> the key of `paged_stats` it reads
SLOT_STEP_KEYS = {
    "sched_page_blocked_share": "slot_steps_page_blocked",
    "sched_ingest_share": "slot_steps_ingesting",
    "sched_drain_out_share": "slot_steps_drain_out",
}


def spans_of(*named):
    """Back-to-back host spans from (name, milliseconds) pairs."""
    out, t = [], 0.0
    for name, ms in named:
        out.append(Event(name, t, ms * 1e-3))
        t += ms * 1e-3
    return out


# 2 s of drain, 4 slots, 10 decode steps: 40 slot-steps, 26 of them
# decoding, 4 ingesting, 6 page-blocked, 3 in the drain-out, 1 other.
CANNED = {
    "window_s": 2.0,
    "slots": 4,
    "decode_steps": 10,
    "step_occupancy_sum": 26,
    "paged": {
        "num_pages": 64, "pages_in_use_peak": 64,
        "slot_steps_ingesting": 4, "slot_steps_page_blocked": 6,
        "slot_steps_drain_out": 3, "slot_steps_free_other": 1,
        "admit_page_blocked_iters": 5,
    },
    "host_spans": spans_of(
        *[("engine_iter", 100.0 + 10.0 * i) for i in range(11)],
        ("dispatch", 12.0), ("dispatch", 8.0),
        ("logits_fetch", 30.0), ("logits_fetch", 20.0),
        ("sample", 4.0), ("sample", 6.0), ("sample", 2.0),
        ("admit", 1.0), ("admit", 3.0),
        ("decode_step", 70.0), ("prefill_chunk", 28.0),
        ("device_wait", 60.0), ("cow", 1.0),
    ),
}
# What a parent commit's run records: the two old spans, the old keys.
PARENT = {
    **CANNED,
    "paged": {"num_pages": 64, "pages_in_use_peak": 64},
    "host_spans": spans_of(("decode_step", 70.0), ("prefill_chunk", 28.0)),
}
EXPECTED = {
    "serve_iter_p90_ms": 190.0,     # 100, 110 ... 200: p90 is the tenth
    "serve_dispatch_share": 1.0,    # 20 ms of 2 s
    "serve_fetch_share": 2.5,
    "serve_sample_share": 0.6,
    "serve_admit_share": 0.2,
    "sched_page_blocked_share": 15.0,  # 6 of 40 slot-steps
    "sched_ingest_share": 10.0,
    "sched_drain_out_share": 7.5,
}


def reader(name):
    return manifest.load_module("per_layer", name).compute


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_canned_record(name):
    assert reader(name)(CANNED) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_a_parents_record(name):
    assert reader(name)(PARENT) is None
    # nor in an untraced run, nor where the engine was not paged
    assert reader(name)({**PARENT, "host_spans": [], "paged": None}) is None


def test_the_eight_are_listed_for_both_serving_cells_under_old_layers():
    m = manifest.load_manifest()
    entries = {x["name"]: x for x in m["per_layer"]}
    other_layers = {x["layer"] for x in m["per_layer"]
                    if x["name"] not in EXPECTED}
    for name in EXPECTED:
        x = entries[name]
        assert x["layer"] in other_layers and x["better"] == "lower"
        assert x["workloads"] == ["gpt2s_serve_chat", "gpt2s_serve_doc"]
        assert x["source"] == ("program_counter" if name in SLOT_STEP_KEYS
                               else "program_span")


def test_slot_step_shares_and_occupancy_add_up_on_the_canned_record():
    total = reader("sched_slot_occupancy")(CANNED) + sum(
        reader(name)(CANNED) for name in SLOT_STEP_KEYS
    )
    assert total == pytest.approx(100.0 - 100.0 * 1 / 40)


@pytest.mark.parametrize("workload", ["gpt2s_serve_chat", "gpt2s_serve_doc"])
def test_a_traced_rehearsal_carries_the_eight_and_they_add_up(
        capsys, workload):
    from distributed_model_parallel_tpu.observability.metrics import (
        TRACE_EVENT_NAMES,
    )

    capsys.readouterr()
    rc = run.main(
        ["--workload", workload, "--seed", "3000000011", "--seconds", "2",
         "--trace", "1", "--rehearsal"],
        t_process=time.perf_counter(),
    )
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(EXPECTED) <= set(got)
    assert all(got[name] >= 0.0 for name in EXPECTED)
    paged = info["paged"]
    if workload == "gpt2s_serve_chat":
        # the chat mix never meets the pool's end
        assert got["sched_page_blocked_share"] == 0.0
        assert paged["admit_page_blocked_iters"] == 0
    # exact counts: every slot-step of a decode step has one name, so
    # the old metric (the decoding ones) gives the number of them all
    counts = {name: paged[key] for name, key in SLOT_STEP_KEYS.items()}
    empty = sum(counts.values()) + paged["slot_steps_free_other"]
    slot_steps = empty / (1.0 - got["sched_slot_occupancy"] / 100.0)
    assert slot_steps == pytest.approx(round(slot_steps))
    for name, count in counts.items():
        assert got[name] == pytest.approx(100.0 * count / slot_steps)
    assert got["sched_slot_occupancy"] + sum(
        got[name] for name in counts
    ) == pytest.approx(
        100.0 - 100.0 * paged["slot_steps_free_other"] / slot_steps
    )
    # every idle gap is named by a span of the program, the innermost
    for name, seconds in line["breakdown"]["idle_gaps"]:
        assert name in TRACE_EVENT_NAMES or name == NO_SPAN, name
