"""Metrics registry (INTERNALS.md §14): the ONE percentile rule
pinned bit-equal to numpy, histogram exact/streaming modes with the
documented streaming bound, the disabled path's zero-allocation pin,
the Prometheus exposition against a committed golden file, and the
serving scheduler's latency report regression-pinned to the retired
hand-rolled numpy math on canned latencies."""

import json
import os
import random

import numpy as np
import pytest

from distributed_model_parallel_tpu.observability import metrics
from distributed_model_parallel_tpu.observability.metrics import (
    GROWTH,
    Histogram,
    MetricsRegistry,
    exact_quantile,
)

GOLDEN_PROM = os.path.join(
    os.path.dirname(__file__), "golden", "metrics.prom"
)
GOLDEN_JSON = os.path.join(
    os.path.dirname(__file__), "golden", "obsreport_metrics.json"
)


def build_golden_registry() -> MetricsRegistry:
    """The exact canned series the committed exposition goldens pin
    (the generator that wrote the goldens invoked this builder)."""
    reg = MetricsRegistry(enabled=True)
    for v in (0.02, 0.02, 0.02, 0.02):
        reg.observe("train_step_s", v)
    for v in (0.01, 0.01, 0.01, 0.01):
        reg.observe("train_fetch_s", v)
    for v in (0.01, 0.02, 0.04, 0.08, 0.16):
        reg.observe("serve_token_s", v)
    for v in (0.05, 0.06, 0.07):
        reg.observe("serve_ttft_s", v)
    reg.inc("train_batches_total", 4)
    reg.inc("serve_tokens_total", 5)
    reg.gauge("serve_goodput", 0.75)
    reg.gauge("serve_batch_occupancy", 2)
    return reg


# ------------------------------------------------------- ONE quantile


def test_exact_quantile_matches_numpy_percentile():
    """The shared rule is bit-equal to numpy's default linear method —
    the regression pin that let the scheduler drop its private numpy
    calls."""
    rng = random.Random(0)
    for n in (1, 2, 3, 5, 17, 100):
        xs = [rng.uniform(0.0, 50.0) for _ in range(n)]
        for q in (0, 25, 50, 90, 99, 100):
            assert exact_quantile(xs, q) == pytest.approx(
                float(np.percentile(np.asarray(xs), q)), rel=1e-12
            )
    assert exact_quantile([], 50) is None


def test_scheduler_latency_report_pinned_to_numpy_on_canned_latencies():
    """The dedupe satellite's pin: the report built through the shared
    histogram math equals the old hand-rolled numpy output
    (round(np.percentile(xs, q) * 1e3, 3)) on canned latencies."""
    from distributed_model_parallel_tpu.serving.scheduler import (
        FinishedSequence,
        Scheduler,
    )

    sched = Scheduler(num_slots=2, max_len=32)
    canned = [
        ([0.011, 0.013, 0.012], 0.051),
        ([0.017, 0.010], 0.043),
        ([0.021, 0.009, 0.014, 0.030], 0.087),
    ]
    for i, (decode, prefill) in enumerate(canned):
        sched.finished.append(FinishedSequence(
            rid=i, prompt_len=4, tokens=[1] * len(decode),
            prefill_s=prefill, decode_s=list(decode),
            total_s=prefill + sum(decode),
        ))
    sched.step_occupancy = [2, 2, 1, 1]
    rep = sched.latency_report()
    decode_all = np.asarray([t for d, _ in canned for t in d])
    prefill_all = np.asarray([p for _, p in canned])
    for key, xs, q in (
        ("decode_p50_ms", decode_all, 50),
        ("decode_p99_ms", decode_all, 99),
        ("prefill_p50_ms", prefill_all, 50),
        ("prefill_p99_ms", prefill_all, 99),
    ):
        assert rep[key] == round(float(np.percentile(xs, q)) * 1e3, 3)
    assert rep["goodput"] == pytest.approx(6 / 8)


# ---------------------------------------------------------- histogram


def test_histogram_exact_small_n_quantiles():
    h = Histogram()
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    for v in xs:
        h.observe(v)
    assert not h.streaming
    for q in (0, 50, 90, 100):
        assert h.quantile(q) == pytest.approx(
            float(np.percentile(xs, q)), rel=1e-12
        )
    assert h.count == 5 and h.vmin == 1.0 and h.vmax == 5.0


def test_histogram_streaming_large_n_bound():
    """Past the exact cap the histogram folds into log buckets; the
    documented bound is sqrt(GROWTH)-1 relative error vs the exact
    quantile (geometric bucket midpoints)."""
    rng = random.Random(7)
    h = Histogram(exact_cap=100)
    xs = [rng.lognormvariate(-4.0, 1.0) for _ in range(5000)]
    for v in xs:
        h.observe(v)
    assert h.streaming
    bound = GROWTH ** 0.5 - 1.0
    for q in (50, 90, 99):
        exact = float(np.percentile(xs, q))
        got = h.quantile(q)
        assert abs(got - exact) / exact <= bound + 1e-3, (
            f"p{q}: streaming {got} vs exact {exact} exceeds the "
            f"{bound:.3%} bound"
        )
    assert h.count == 5000
    assert h.total == pytest.approx(sum(xs))


def test_histogram_streaming_mode_flip_and_zero_bucket():
    h = Histogram(exact_cap=3)
    for v in (0.0, 1.0, 2.0, 3.0):  # 4th sample trips streaming
        h.observe(v)
    assert h.streaming
    assert h.quantile(0) == 0.0  # zero bucket answers exactly 0
    assert h.quantile(100) >= 2.0


# ----------------------------------------------------------- registry


def test_disabled_registry_is_zero_allocation_single_branch():
    """The acceptance pin: the disabled path allocates NO instruments
    — one branch per site, nothing to pay for leaving the wiring in
    hot loops permanently."""
    reg = MetricsRegistry(enabled=False)
    reg.observe("train_step_s", 1.0)
    reg.inc("train_batches_total")
    reg.gauge("serve_goodput", 0.5)
    assert len(reg) == 0
    assert reg._hists == {} and reg._counters == {} and reg._gauges == {}
    assert reg.histogram("train_step_s") is None
    # Enabling starts recording without any reconstruction.
    reg.enabled = True
    reg.observe("train_step_s", 1.0)
    assert len(reg) == 1


def test_registry_thread_safety():
    import threading

    reg = MetricsRegistry(enabled=True)

    def work():
        for _ in range(200):
            reg.observe("train_step_s", 0.001)
            reg.inc("train_batches_total")

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.histogram("train_step_s").count == 800
    assert reg.to_json()["counters"]["train_batches_total"] == 800


def test_prometheus_exposition_golden():
    """Byte-stable exposition for the canned registry — counters and
    gauges as singles, histograms as summaries (p50/p90/p99 + _sum/
    _count), sorted, HELP lines from the documented registry."""
    got = build_golden_registry().to_prometheus()
    with open(GOLDEN_PROM) as f:
        assert got == f.read()
    # Structural spot checks independent of the golden bytes.
    assert "# TYPE serve_token_s summary" in got
    assert "# TYPE serve_goodput gauge" in got
    assert "# TYPE train_batches_total counter" in got
    assert 'serve_token_s{quantile="0.5"} 0.04' in got


def test_json_export_golden_and_roundtrip(tmp_path):
    reg = build_golden_registry()
    with open(GOLDEN_JSON) as f:
        assert reg.to_json() == json.load(f)
    path = reg.export(str(tmp_path / "m.json"))
    with open(path) as f:
        assert json.load(f) == reg.to_json()
    prom = reg.export(str(tmp_path / "m.prom"))
    with open(prom) as f:
        assert f.read() == reg.to_prometheus()


def test_global_registry_swap_and_env_default(monkeypatch):
    metrics.set_metrics(None)
    monkeypatch.delenv("DMP_METRICS", raising=False)
    try:
        assert metrics.get_metrics().enabled is False
        inj = MetricsRegistry(enabled=True)
        metrics.set_metrics(inj)
        assert metrics.get_metrics() is inj
    finally:
        metrics.set_metrics(None)


# ------------------------------------------------- documented registry


def test_every_emitted_name_is_documented():
    """Unit twin of the conftest META-CHECK: scanning the package for
    span/counter/metric emission sites finds no undocumented name."""
    assert metrics.scan_emitted_names() == {}


@pytest.mark.parametrize("name", sorted(metrics.METRIC_NAMES))
def test_documented_metric_is_emitted(name):
    """The other direction: no dead name in the registry. A tiny run of
    the program with the registry on (`test_observability.py` makes
    them, once per process) leaves each documented series behind."""
    import test_observability as runs

    assert any(
        name in series
        for run in (
            runs.trainer_epoch, runs.fsdp_plan_epoch,
            runs.held_experts_epoch, runs.paged_drain,
            runs.speculative_drain,
        )
        for series in run().metrics.values()
    )


def test_scanner_catches_a_stray(tmp_path):
    """The META-CHECK actually bites: a call site with an unknown
    literal name is reported with its file:line."""
    pkg = tmp_path / "straypkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'def f(mx, tracer):\n'
        '    mx.observe("totally_undocumented_metric", 1.0)\n'
        '    with tracer.span("totally_undocumented_span"):\n'
        '        pass\n'
    )
    strays = metrics.scan_emitted_names(str(tmp_path))
    assert set(strays) == {
        "totally_undocumented_metric", "totally_undocumented_span",
    }
    assert strays["totally_undocumented_metric"] == ["straypkg/mod.py:2"]
