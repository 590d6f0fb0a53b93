"""The measured half of observability (INTERNALS.md §14): trace
attribution (per-phase table, nested-span union, the explicit
unattributed residual), measured-vs-predicted reconciliation keyed on
ledger combos, calibration (features pinned equal to the cost
engine's closed forms; synthetic round-trip recovers known constants
within 1%), and the obsreport golden pipeline (canned inputs ->
byte-stable report; --pregate exit-5 semantics). All jax-free."""

import gzip
import json
import os

import pytest

from distributed_model_parallel_tpu.observability import (
    attribution,
    calibrate,
    cost,
    report,
    trace,
)
from distributed_model_parallel_tpu.observability.metrics import (
    TRACE_EVENT_NAMES,
)

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")
GOLDEN_TRACE = os.path.join(GOLDEN_DIR, "obsreport_trace.json")
GOLDEN_REPORT = os.path.join(GOLDEN_DIR, "obsreport_report.txt")
GOLDEN_LEDGER = os.path.join(GOLDEN_DIR, "obsreport_ledger.json")
GOLDEN_CALIBRATION = os.path.join(
    GOLDEN_DIR, "obsreport_calibration.json"
)
GOLDEN_METRICS = os.path.join(GOLDEN_DIR, "obsreport_metrics.json")

#: The residual bound the golden trace is pinned under (acceptance:
#: "unattributed residual <= a stated bound on the golden trace") —
#: the canned timeline leaves 2 ms of un-spanned host bookkeeping per
#: training iteration, 8 of 93 ms total.
GOLDEN_RESIDUAL_BOUND = 0.10


class ManualClock:
    """Advances only when told — the golden timeline's clock."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float) -> None:
        self.t += dt


def build_golden_obs_trace() -> trace.Tracer:
    """One synthetic run emitting EVERY span PR 12 wires (the trainer
    fetch/step/sync/checkpoint trio, the checkpoint writer pair, the
    serving engine + scheduler set) on a deterministic clock — the
    obsreport pre-gate's canned input (the generator that wrote
    tests/golden/obsreport_trace.json invoked this builder)."""
    clock = ManualClock()
    t = trace.Tracer(clock=clock, enabled=True)
    for _i in range(4):
        with t.span("fetch", want=1):
            clock.tick(0.010)
        with t.span("step", n=1):
            clock.tick(0.005)
        clock.tick(0.002)  # host bookkeeping NO span covers (residual)
        with t.span("sync"):
            clock.tick(0.003)
    with t.span("checkpoint_blocked", snapshot="last", epoch=0):
        with t.span("ckpt_snapshot", snapshot="last", save_id=1):
            clock.tick(0.004)
        clock.tick(0.001)
    t.complete(
        "ckpt_background_write", clock.t, clock.t + 0.006, tid=1,
    )
    # One pass of the paged loop as serving/engine.py::_run_paged
    # records it (the two 2 ms ticks it always had, split among the
    # children), with the speculative pair riding in the same pass so
    # the wall and the residual stay what they were.
    t_iter = clock.t
    with t.span("admit"):
        clock.tick(0.00025)
    with t.span("prefill_chunk", slot=0, start=0):
        with t.span("dispatch"):
            clock.tick(0.00025)
        with t.span("device_wait"):
            clock.tick(0.00075)
        with t.span("logits_fetch"):
            clock.tick(0.00025)
        with t.span("sample"):
            clock.tick(0.00025)
    with t.span("cow"):
        clock.tick(0.00025)
    with t.span("decode_step", active=2):
        with t.span("dispatch"):
            clock.tick(0.00025)
        with t.span("device_wait"):
            clock.tick(0.001)
        with t.span("logits_fetch"):
            clock.tick(0.00025)
    with t.span("sample"):
        clock.tick(0.0005)
    with t.span("draft_round", active=2, k=2):
        clock.tick(0.002)
    with t.span("verify_step", active=2):
        clock.tick(0.002)
    t.complete("engine_iter", t_iter, clock.t,
               waiting=0, ingesting=1, active=2)
    t.counter("batch_occupancy", 2)
    tid = t.track_id("request 'r0'")
    t.complete("queued", 0.0, 0.004, tid=tid)
    t.complete("prefill", 0.004, 0.012, tid=tid, prompt_len=4)
    t.complete("decode", 0.012, 0.030, tid=tid, tokens=3)
    return t


def build_golden_ledger() -> dict:
    """The canned ledger the pre-gate reconciles against: one combo
    whose predicted step time equals the golden trace's measured
    per-step sync (3 ms), under the CURRENT constants."""
    return {
        "constants": {**cost.CONSTANTS, **cost.COMPUTE_CONSTANTS},
        "tolerance": 0.05,
        "combos": {"golden/S2": {
            "predicted_step_s": 0.003,
            "alpha_s": 0.0002,
            "beta_s": 0.0028,
            "n_collectives": 4,
        }},
    }


# -------------------------------------------------------- attribution


def test_golden_trace_file_matches_builder():
    """The committed canned trace IS the builder's output — the
    pre-gate input can never silently drift from what the tracer
    would record."""
    with open(GOLDEN_TRACE) as f:
        assert build_golden_obs_trace().to_chrome() == json.load(f)


def test_attribution_covers_every_pr12_span_with_bounded_residual():
    """The acceptance pin: the attribution table covers every phase
    span PR 12 emits and the unattributed residual on the golden
    trace stays under the stated bound."""
    attr = attribution.attribute(
        build_golden_obs_trace().to_chrome()
    )
    span_names = set(TRACE_EVENT_NAMES) - {"batch_occupancy"}  # counter
    assert {p.name for p in attr.phases} == span_names
    assert 0 < attr.residual_share <= GOLDEN_RESIDUAL_BOUND
    assert attr.residual_ms == pytest.approx(8.0, abs=1e-3)
    assert attr.wall_ms == pytest.approx(93.0, abs=1e-3)
    assert attr.main_tid == 0


def test_attribution_union_does_not_double_count_nested_spans():
    """ckpt_snapshot nests inside checkpoint_blocked on the main
    track; the covered union must count that interval once."""
    attr = attribution.attribute(
        build_golden_obs_trace().to_chrome()
    )
    assert attr.covered_ms == pytest.approx(85.0, abs=1e-3)
    snap = attr.phase("ckpt_snapshot")
    blocked = attr.phase("checkpoint_blocked")
    assert snap.total_ms == pytest.approx(4.0, abs=1e-3)
    assert blocked.total_ms == pytest.approx(5.0, abs=1e-3)


def test_reconcile_measured_vs_predicted_rows():
    attr = attribution.attribute(
        build_golden_obs_trace().to_chrome()
    )
    rows = attribution.reconcile(
        attr, build_golden_ledger(), ["golden/S2", "absent/S8"]
    )
    hit, miss = rows
    assert hit["combo"] == "golden/S2"
    assert hit["predicted_ms"] == pytest.approx(3.0)
    assert hit["measured_sync_ms_per_step"] == pytest.approx(3.0)
    assert hit["delta_pct"] == pytest.approx(0.0)
    assert hit["steps"] == 4
    assert miss["predicted_ms"] is None and miss["delta_pct"] is None


def test_load_trace_gz_and_profile_dir_scan(tmp_path):
    """xplane-style traces load through the same path: gzipped, bare
    event-list container, found by the --profile-dir scan."""
    events = build_golden_obs_trace().to_chrome()["traceEvents"]
    prof = tmp_path / "plugins" / "profile" / "2026_08_04"
    prof.mkdir(parents=True)
    path = prof / "host.trace.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump(events, f)  # bare list, as xplane writes it
    hits = attribution.profile_dir_traces(str(tmp_path))
    assert hits == [str(path)]
    chrome = attribution.load_trace(hits[0])
    assert attribution.attribute(chrome).n_events > 0
    with pytest.raises(ValueError):
        bad = tmp_path / "not_a_trace.json"
        bad.write_text("{}")
        attribution.load_trace(str(bad))


# -------------------------------------------------------- calibration


def test_calibration_features_match_closed_forms():
    """Each feature decomposition, evaluated under the hand
    constants, reproduces cost.py's closed form to float precision —
    the fit target and the prose model can never drift."""
    c = cost.CONSTANTS
    cases = [
        (calibrate.ring_all_reduce_features(100e6, 64, n_ops=161),
         cost.ring_all_reduce_s(100e6, 64, n_ops=161)),
        (calibrate.two_level_features(100e6, 32, 2, n_buckets=4),
         cost.two_level_all_reduce_s(100e6, 32, 2, n_buckets=4)),
        (calibrate.two_level_features(100e6, 32, 2, n_buckets=4,
                                      wire="int8"),
         cost.two_level_all_reduce_s(100e6, 32, 2, n_buckets=4,
                                     wire="int8")),
        (calibrate.flat_all_to_all_features(12_500_000, 2, 32, 2),
         cost.flat_all_to_all_s(12_500_000, 2, 32, 2)),
        (calibrate.hierarchical_all_to_all_features(
            12_500_000, 2, 32, 2, wire="int8"),
         cost.hierarchical_all_to_all_s(12_500_000, 2, 32, 2,
                                        wire="int8")),
    ]
    for row, want in cases:
        assert calibrate.features_to_seconds(row, c) == pytest.approx(
            want, rel=1e-12
        ), row.name


def test_calibration_roundtrip_recovers_constants_within_1pct():
    """The acceptance pin: rows synthesized from KNOWN constants (plus
    a constant compute intercept) fit back to those constants within
    1%."""
    true = {
        "alpha_hop_s": 2e-6,
        "bw_ici_effective_bytes_per_s": 8e10,
        "alpha_dcn_hop_s": 2.5e-5,
        "bw_dcn_effective_bytes_per_s": 2e10,
    }
    intercept = 1e-4
    rows = []
    for s in (2, 4, 8, 16):
        for wire in ("none", "bf16", "int8"):
            for nbytes in (1.5e6, 24e6):
                r = calibrate.two_level_features(
                    nbytes, ici=max(s // 2, 1), dcn=2,
                    n_buckets=2, wire=wire,
                )
                r.measured_s = (
                    calibrate.features_to_seconds(r, true) + intercept
                )
                rows.append(r)
        r = calibrate.hierarchical_all_to_all_features(
            1e6, 4, ici=max(s // 2, 1), dcn=2,
        )
        r.measured_s = calibrate.features_to_seconds(r, true) + intercept
        rows.append(r)
    fit = calibrate.fit_constants(rows)
    for key, want in true.items():
        got = fit["constants"][key]
        assert abs(got - want) / want < 0.01, (key, got, want)
    assert fit["intercepts_s"]["rows"] == pytest.approx(
        intercept, rel=0.01
    )
    assert fit["residual_rms_s"] < 1e-9


def test_calibration_underdetermined_rows_refused():
    r = calibrate.two_level_features(1e6, 4, 2)
    r.measured_s = 1e-3
    with pytest.raises(ValueError, match="cannot identify"):
        calibrate.fit_constants([r])


def test_rows_from_committed_bench_and_fit():
    """The committed CPU-mesh bench artifact yields fit-able rows
    (the loop the committed experiments/calibration.json closed)."""
    with open(os.path.join(
        os.path.dirname(GOLDEN_DIR), "..", "BENCH_r06.json"
    )) as f:
        rows = calibrate.rows_from_bench(json.load(f))
    assert len(rows) >= 12
    sources = {r.source for r in rows}
    assert {"reducer", "moe"} <= sources
    fit = calibrate.fit_constants(rows)
    assert set(fit["constants"]) == set(cost.CONSTANTS)
    drift = calibrate.drift_report(fit["constants"])
    assert set(drift) == set(cost.CONSTANTS)


def test_committed_calibration_loads_and_reports_drift():
    """experiments/calibration.json is a valid, loadable artifact;
    cost.load_calibration validates it and drift_report prices it
    against the committed constants."""
    path = os.path.join(
        os.path.dirname(GOLDEN_DIR), "..", "experiments",
        "calibration.json",
    )
    constants = cost.load_calibration(path)
    assert set(constants) == set(cost.CONSTANTS)
    with open(path) as f:
        payload = json.load(f)
    assert payload["version"] == calibrate.CALIBRATION_VERSION
    assert set(payload["drift_pct"]) == set(cost.CONSTANTS)


def test_load_calibration_refuses_partial_constants(tmp_path):
    path = tmp_path / "cal.json"
    path.write_text(json.dumps({
        "version": calibrate.CALIBRATION_VERSION,
        "constants": {"alpha_hop_s": 1e-6},
    }))
    with pytest.raises(ValueError, match="missing constants"):
        cost.load_calibration(str(path))
    path.write_text(json.dumps({"not": "a calibration"}))
    with pytest.raises(ValueError, match="calibration"):
        cost.load_calibration(str(path))


def test_costgate_calibration_flag_reports_never_gates(capsys):
    """`tools/costgate --calibration` prints the fitted-vs-committed
    drift and carries it in the summary JSON without affecting the
    exit code; an unreadable file is a usage error (2)."""
    from distributed_model_parallel_tpu.observability import costgate

    cal = os.path.join(
        os.path.dirname(GOLDEN_DIR), "..", "experiments",
        "calibration.json",
    )
    rc = costgate.main([
        "--filter", "cm_ag/S2", "--calibration", cal,
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "calibration drift (reported, not gated)" in out
    summary = json.loads(out.strip().splitlines()[-1])
    assert set(summary["costgate"]["calibration_drift_pct"]) == set(
        cost.CONSTANTS
    )
    assert costgate.main([
        "--filter", "cm_ag/S2",
        "--calibration", "/no/such/calibration.json",
    ]) == 2


# ---------------------------------------------------------- obsreport


def _golden_inputs():
    with open(GOLDEN_METRICS) as f:
        metrics_json = json.load(f)
    with open(GOLDEN_CALIBRATION) as f:
        calibration = json.load(f)
    return metrics_json, calibration


def test_render_report_golden_bytes():
    """The pre-gate's contract, run in-process: canned inputs render
    to the committed golden report byte-for-byte."""
    metrics_json, calibration = _golden_inputs()
    got = report.render_report(
        build_golden_obs_trace().to_chrome(),
        metrics=metrics_json,
        ledger=build_golden_ledger(),
        combos=report.PREGATE_COMBOS,
        calibration=calibration,
    )
    with open(GOLDEN_REPORT) as f:
        assert got == f.read()
    # Every PR 12 phase span appears in the rendered table.
    for name in set(TRACE_EVENT_NAMES) - {"batch_occupancy"}:
        assert f"\n{name}" in got
    assert "unattributed residual" in got
    assert "golden/S2" in got


def test_golden_ledger_file_matches_builder():
    with open(GOLDEN_LEDGER) as f:
        assert build_golden_ledger() == json.load(f)


def test_report_json_twin():
    metrics_json, calibration = _golden_inputs()
    out = report.report_json(
        build_golden_obs_trace().to_chrome(),
        metrics=metrics_json,
        ledger=build_golden_ledger(),
        combos=["golden/S2"],
        calibration=calibration,
    )
    assert out["attribution"]["residual_ms"] == pytest.approx(
        8.0, abs=1e-3
    )
    assert out["measured_vs_predicted"][0]["delta_pct"] == 0.0
    assert set(out["calibration_drift"])


def test_obsreport_pregate_ok(capsys):
    assert report.main(["--pregate"]) == 0
    out = capsys.readouterr().out
    assert '"pregate": "ok"' in out


def test_obsreport_pregate_mismatch_exits_5(tmp_path, monkeypatch,
                                            capsys):
    bad = tmp_path / "golden.txt"
    bad.write_text("definitely not the report\n")
    monkeypatch.setitem(
        report.PREGATE_INPUTS, "golden", str(bad)
    )
    assert report.main(["--pregate"]) == report.EXIT_GOLDEN_MISMATCH
    out = capsys.readouterr().out
    assert "golden mismatch at line 1" in out


def test_obsreport_cli_end_to_end(tmp_path, capsys):
    """The non-pregate surface: --trace/--metrics/--ledger/--combo/
    --calibration render the same report; --json emits the twin."""
    tpath = tmp_path / "t.json"
    build_golden_obs_trace().export(str(tpath))
    lpath = tmp_path / "l.json"
    lpath.write_text(json.dumps(build_golden_ledger()))
    rc = report.main([
        "--trace", str(tpath),
        "--metrics", GOLDEN_METRICS,
        "--ledger", str(lpath), "--combo", "golden/S2",
        "--calibration", GOLDEN_CALIBRATION,
        "--out", str(tmp_path / "rep.txt"),
    ])
    assert rc == 0
    out = capsys.readouterr().out
    with open(GOLDEN_REPORT) as f:
        assert out == f.read()
    with open(tmp_path / "rep.txt") as f:
        assert f.read() == out
    assert report.main([
        "--trace", str(tpath), "--json",
    ]) == 0
    assert report.main([]) == 2  # no trace source
