"""The four per-layer metrics that read the drain's timeline (ISSUE 38):
each reader on a canned record, on the record of a program that keeps
no timeline (a parent commit) and of an engine that is not paged, the
manifest's entries, and `--rehearsal` runs of the four serving cells,
traced (the four readers on the run's own record; in the line where the
manifest lists them) and untraced (the `info` line's `paged.timeline`,
which every timed run prints).

The entries list the two GPT-2 cells alone: `test_bench_jamba.py` and
`test_bench_glm.py` hold the per-layer lists of the other two serving
cells to what they were, and a PR that adds metrics may edit no file
the benchmark has (`PERF.md` section 7)."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import manifest  # noqa: E402
from benchmark.harness.stats import samples_beyond  # noqa: E402

LISTED_CELLS = ["gpt2s_serve_chat", "gpt2s_serve_doc"]
SERVING_CELLS = LISTED_CELLS + ["jamba2_serve_docs", "glm47f_serve_longdoc"]
# Ten gaps beyond a 99th percentile (`stats.MIN_TAIL_SAMPLES`).
MIN_GAPS_FOR_P99 = 1000
# metric -> (layer, the end-to-end metric it moves)
ENTRIES = {
    "serve_itl_p50_ms": ("serving_host_loop", "serve_tpot_p50_ms"),
    "serve_itl_p99_ms": ("scheduler", "serve_tpot_p50_ms"),
    "serve_itl_max_ms": ("serving_host_loop", "serve_out_tok_s"),
    "sched_first_token_p50_ms": ("scheduler", "serve_out_tok_s"),
}
# A drain of 1,200 gaps as `Scheduler.timeline` leaves it.
TIMELINE = {
    "tokens": 1300, "gaps": 1200,
    "itl_ms": {"p50": 57.25, "p90": 61.5, "p99": 118.125, "max": 204.5,
               "mean": 58.0},
    "itl_max_at_s": 12.5, "itl_max_rid": "17", "decode_span_s": 69.6,
    "first_token_ms": {"p50": 310.5, "p90": 702.0, "max": 911.0},
    "queued_ms": {"p50": 9000.0, "p90": 21000.0, "max": 24000.0},
    "passes": 520, "pass_ms": {"p50": 57.0, "p90": 60.0, "max": 204.0},
    "wall_s": 30.0, "stretch_s": {"prefill_chunk": 18.0, "decode_step": 11.5},
    "host_s": 0.5,
    "gc": {"collections": 3, "pause_s": 0.004, "pause_max_ms": 2.0},
    "longest_passes": [
        {"index": 211, "at_s": 12.3, "wall_ms": 204.0, "per_launch_ms": 29.143,
         "chunks": 6, "decoding": 30, "waiting": 88, "host_ms": 1.5},
    ],
    "slowest_passes": [
        {"index": 402, "at_s": 23.9, "wall_ms": 96.0, "per_launch_ms": 96.0,
         "chunks": 0, "decoding": 31, "waiting": 0, "host_ms": 0.5},
    ],
}
CANNED = {"paged": {"num_pages": 64, "timeline": TIMELINE}}
EXPECTED = {
    "serve_itl_p50_ms": 57.25,
    "serve_itl_p99_ms": 118.125,
    "serve_itl_max_ms": 204.5,
    "sched_first_token_p50_ms": 310.5,
}
# What a parent commit's run records: page accounting, no timeline.
PARENT = {"paged": {"num_pages": 64, "pages_in_use_peak": 64}}


def reader(name):
    return manifest.load_module("per_layer", name).compute


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_canned_record(name):
    assert reader(name)(CANNED) == EXPECTED[name]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_in_a_parents_record(name):
    assert reader(name)(PARENT) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_finds_nothing_where_the_engine_was_not_paged(name):
    assert reader(name)({"paged": None}) is None


def test_the_p99_wants_ten_gaps_beyond_it():
    assert samples_beyond(MIN_GAPS_FOR_P99, 99.0) == 10
    few = {"paged": {"timeline": {**TIMELINE, "gaps": MIN_GAPS_FOR_P99 - 1}}}
    assert reader("serve_itl_p99_ms")(few) is None
    assert reader("serve_itl_p50_ms")(few) == 57.25
    # a drain of one-token requests has no gap to report at all
    none = {"paged": {"timeline": {
        **TIMELINE, "gaps": 0,
        "itl_ms": dict.fromkeys(TIMELINE["itl_ms"]),
    }}}
    assert reader("serve_itl_max_ms")(none) is None
    assert reader("sched_first_token_p50_ms")(none) == 310.5


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_entry_names_its_layer_and_the_cells_that_may_list_it(name):
    m = manifest.load_manifest()
    entry = next(x for x in m["per_layer"] if x["name"] == name)
    layer, moves = ENTRIES[name]
    assert entry == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "program_counter", "layer": layer, "moves": moves,
        "workloads": LISTED_CELLS,
    }
    # appended: what the benchmark had keeps its place
    assert [x["name"] for x in m["per_layer"]][-4:] == list(ENTRIES)
    reports = {x["name"]: x.get("workloads") for x in m["end_to_end"]}
    assert set(SERVING_CELLS) <= set(reports[moves])


def rehearse(capsys, workload, trace):
    """(result line, info) of one `--rehearsal` run."""
    capsys.readouterr()
    rc = run.main(
        ["--workload", workload, "--seed", "3800000011", "--seconds", "2",
         "--trace", str(trace), "--rehearsal"],
        t_process=time.perf_counter(),
    )
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), json.loads(lines[-2])["info"]


@pytest.mark.parametrize("workload", SERVING_CELLS)
def test_a_traced_rehearsal_gives_the_four_readers_something_to_read(
        capsys, records, workload):
    line, info = rehearse(capsys, workload, trace=1)
    (_, record), = records
    got = {name: reader(name)(record) for name in ENTRIES}
    got = {name: value for name, value in got.items() if value is not None}
    printed = {k: v["value"] for k, v in line["metrics"].items()
               if k in ENTRIES}
    assert printed == (got if workload in LISTED_CELLS else {})
    t = info["paged"]["timeline"]
    assert t["tokens"] - t["gaps"] == line["attempted"]  # one first each
    assert got["serve_itl_p50_ms"] == t["itl_ms"]["p50"] > 0.0
    assert got["serve_itl_max_ms"] == t["itl_ms"]["max"]
    assert got["sched_first_token_p50_ms"] == t["first_token_ms"]["p50"] > 0.0
    # a toy drain has a few hundred gaps: the p99 is held back below
    # its floor and printed at or over it
    if t["gaps"] >= MIN_GAPS_FOR_P99:
        assert got["serve_itl_p99_ms"] == t["itl_ms"]["p99"]
    else:
        assert "serve_itl_p99_ms" not in got, t["gaps"]
    assert got["serve_itl_p50_ms"] <= t["itl_ms"]["p99"] <= (
        got["serve_itl_max_ms"]
    )
    assert all(line["metrics"][name]["unit"] == "ms" for name in printed)


@pytest.mark.parametrize("workload", ["gpt2s_serve_chat", "jamba2_serve_docs"])
def test_an_untraced_rehearsals_info_line_carries_the_timeline(
        capsys, workload):
    line, info = rehearse(capsys, workload, trace=0)
    assert not set(ENTRIES) & set(line["metrics"])  # per-layer: traced runs
    t = info["paged"]["timeline"]
    assert t["passes"] >= len(t["longest_passes"]) == 5
    walls = [p["wall_ms"] for p in t["longest_passes"]]
    assert walls == sorted(walls, reverse=True)
    assert walls[0] == t["pass_ms"]["max"]
    per_launch = [p["per_launch_ms"] for p in t["slowest_passes"]]
    assert per_launch == sorted(per_launch, reverse=True)
    assert all(
        set(p) == {"index", "at_s", "wall_ms", "per_launch_ms", "chunks",
                   "decoding", "waiting", "host_ms"}
        for p in t["longest_passes"] + t["slowest_passes"]
    )
    # the pass walls divide into the two stretches and the host, and the
    # drain's wall holds them
    stretches = sum(t["stretch_s"].values())
    assert t["host_s"] + stretches == pytest.approx(t["wall_s"], abs=3e-6)
    assert 0.0 < t["host_s"] < t["wall_s"] <= info["window_s"]
    assert t["gc"]["collections"] >= 0 and t["gc"]["pause_s"] < t["wall_s"]
    # (the mean is printed to the microsecond)
    assert t["itl_ms"]["mean"] * t["gaps"] == pytest.approx(
        1e3 * t["decode_span_s"], abs=1e-3 * t["gaps"]
    )
    assert t["itl_max_rid"] is not None and 0.0 < t["itl_max_at_s"] < (
        info["window_s"]
    )
