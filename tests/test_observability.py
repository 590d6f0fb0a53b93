"""Observability subsystem (INTERNALS.md §13): the span tracer's
nesting/export contract against a committed Chrome-trace golden file
(deterministic clock injected — no wall time in any assertion), the
static cost engine's closed-form predictions pinned for hand-computed
combos, the costgate's regression/missing-row/tolerance semantics as
pure-function tests, and a Trainer-phase-timing smoke on the virtual
mesh."""

import json
import os

import numpy as np
import pytest

from distributed_model_parallel_tpu.observability import (
    cost,
    metrics,
    trace,
)
from distributed_model_parallel_tpu.observability.costgate import (
    gate_check,
    make_ledger,
)

GOLDEN = os.path.join(
    os.path.dirname(__file__), "golden", "chrome_trace.json"
)


class FakeClock:
    """Deterministic injected clock: 1.0, 2.0, 3.0, ... seconds."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        self.t += 1.0
        return self.t


def build_golden_tracer() -> trace.Tracer:
    """The exact event sequence the committed golden file pins (also
    invoked by the generator that wrote the golden)."""
    t = trace.Tracer(clock=FakeClock(), enabled=True)
    with t.span("epoch", epoch=0):
        with t.span("step", n=2):
            pass
        t.counter("batch_occupancy", 3)
    t.instant("evict", slot=1)
    tid = t.track_id("request 'r0'")
    t.complete("prefill", 10.0, 12.5, tid=tid, prompt_len=4)
    return t


# ------------------------------------------------------------- tracer


def test_span_nesting_and_chrome_export_golden(tmp_path):
    tracer = build_golden_tracer()
    path = tracer.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        got = json.load(f)  # acceptance: round-trips json.loads
    with open(GOLDEN) as f:
        want = json.load(f)
    assert got == want

    # Structural nesting, independent of the golden bytes: the inner
    # span's [ts, ts+dur) interval is contained in the outer's, on the
    # same track — how Chrome complete events nest.
    spans = {
        e["name"]: e for e in got["traceEvents"] if e["ph"] == "X"
    }
    outer, inner = spans["epoch"], spans["step"]
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    # The named request track is disjoint from thread tracks and its
    # complete event carries the caller-supplied timestamps.
    assert spans["prefill"]["tid"] >= 1000
    assert spans["prefill"]["dur"] == pytest.approx(2.5e6)


def test_disabled_tracer_is_single_branch_noop():
    tracer = trace.Tracer(enabled=False)
    s1 = tracer.span("a", x=1)
    s2 = tracer.span("b")
    assert s1 is s2  # the shared singleton: no per-call allocation
    with s1:
        tracer.counter("c", 1)
        tracer.instant("i")
        tracer.complete("d", 0.0, 1.0)
    assert len(tracer) == 0


def test_tracer_thread_safety_and_thread_tracks():
    import threading

    tracer = trace.Tracer(enabled=True)

    def work():
        for _ in range(50):
            with tracer.span("w"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    with tracer.span("main"):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    events = tracer.to_chrome()["traceEvents"]
    assert len(events) == 4 * 50 + 1
    # Each thread got its own small-ordinal track.
    assert {e["tid"] for e in events} <= set(range(5))


# -------------------------------------------------------- cost engine


def test_cost_flat_ring_hand_computed():
    # 100 MB over a flat 64-ring, 161 unfused ops (the scaling64 §3a
    # shape): beta = 2*63/64 * 100e6/100e9 = 1.96875 ms; alpha =
    # 161 * 2*63 * 1us = 20.286 ms.
    got = cost.ring_all_reduce_s(100e6, 64, n_ops=161)
    assert got == pytest.approx(0.00196875 + 0.020286, rel=1e-12)
    # Bucketed (one fused op) keeps the beta, drops alpha to one ring.
    got = cost.ring_all_reduce_s(100e6, 64, n_ops=1)
    assert got == pytest.approx(0.00196875 + 0.000126, rel=1e-12)


def test_cost_hierarchical_two_level_hand_computed():
    # 100 MB over 2 x 32 dcn x ici, 4 buckets: ici beta 2*31/32 *
    # 100e6/100e9 = 1.9375 ms; dcn beta 2*(1/2) * (100e6/32)/25e9 =
    # 0.125 ms; alpha 4 * (2*31*1us + 2*1*10us) = 0.328 ms.
    got = cost.two_level_all_reduce_s(100e6, 32, 2, n_buckets=4)
    assert got == pytest.approx(
        0.0019375 + 0.000125 + 0.000328, rel=1e-12
    )


def test_cost_int8_wire_hand_computed():
    # Same combo on the int8 wire: the dcn leg quarters (0.03125 ms)
    # and each of the 4 buckets pays one extra sidecar hop pair per
    # payload hop: alpha = 4 * (2*31*1us + 2*2*1*10us) = 0.408 ms.
    got = cost.two_level_all_reduce_s(
        100e6, 32, 2, n_buckets=4, wire="int8"
    )
    assert got == pytest.approx(
        0.0019375 + 0.00003125 + 0.000408, rel=1e-12
    )


def test_cost_moe_exchange_flat_vs_hierarchical():
    # The §3c MoE shape: 12.5M bf16 elements over 2 x 32. The
    # hierarchical exchange drops (K-1)*I = 32 dcn hops to 1 and keeps
    # the dcn bytes equal — so it must be strictly cheaper.
    elems = 12_500_000
    flat = cost.flat_all_to_all_s(elems, 2, 32, 2)
    hier = cost.hierarchical_all_to_all_s(elems, 2, 32, 2)
    assert hier < flat
    # int8 wire quarters only the dcn leg of the bf16 payload.
    hier_int8 = cost.hierarchical_all_to_all_s(
        elems, 2, 32, 2, wire="int8"
    )
    dcn_leg = (1 / 2) * elems * 2 / cost.BW_DCN_EFFECTIVE
    assert hier - hier_int8 == pytest.approx(dcn_leg / 2, rel=1e-9)


def test_cost_plan_bubble_factor_hand_computed():
    """The scheduled-plan bubble (ISSUE 20): (VM+pp-1)/(VM) with V
    only counting for the interleaved schedule and M defaulting to
    pp*V — so gpipe/1f1b twins at one M share a bubble and the
    interleaved twin's is strictly smaller; pp=1 has no bubble."""
    assert cost.plan_bubble_factor(1) == 1.0
    assert cost.plan_bubble_factor(2) == pytest.approx(1.5)  # M=pp
    assert cost.plan_bubble_factor(2, "gpipe", 1, 4) \
        == pytest.approx(1.25)
    assert cost.plan_bubble_factor(2, "1f1b", 1, 4) \
        == pytest.approx(1.25)
    assert cost.plan_bubble_factor(2, "interleaved", 2, 4) \
        == pytest.approx(1.125)
    # default M = pp*V for interleaved: (pp*V*V... ) = (8+1)/8
    assert cost.plan_bubble_factor(2, "interleaved", 2) \
        == pytest.approx(1.125)


def test_cost_composed_plan_step_schedule_terms():
    """`composed_plan_step_s` stays byte-stable for pre-ISSUE-20
    callers (gpipe defaults price the old M+pp-1 wire ticks) and the
    scheduled closed form honestly prices MORE wire ticks
    (2MV + 2(pp-1)) while the compute term folds the bubble — the
    cross-schedule win lives in the lowered tier where comm is
    schedule-symmetric."""
    args = (2, 1, 4, 1_000_000, 4, 128, 64, 1000, 8, 8, 1)
    base = cost.composed_plan_step_s(*args)
    assert base == cost.composed_plan_step_s(
        *args, schedule="gpipe", virtual_stages=1,
        num_microbatches=0, compute_s=0.0,
    )
    sched = cost.composed_plan_step_s(
        *args, schedule="1f1b", num_microbatches=4,
    )
    assert sched > cost.composed_plan_step_s(*args, num_microbatches=4)
    # the compute fold is compute_s * bubble, additively
    with_c = cost.composed_plan_step_s(
        *args, schedule="1f1b", num_microbatches=4, compute_s=1.0,
    )
    assert with_c - sched == pytest.approx(
        cost.plan_bubble_factor(2, "1f1b", 1, 4), rel=1e-9,
    )


def test_predict_collectives_walker_hand_computed():
    """The HLO walker's per-kind pricing on a hand-built module: one
    ring hop within 'ici', one all-reduce crossing 'dcn'."""
    from distributed_model_parallel_tpu.analysis.collectives import (
        MeshModel,
        classify_instruction,
    )
    from distributed_model_parallel_tpu.analysis.hlo import (
        Buffer,
        Instruction,
    )

    mesh = MeshModel(
        axis_names=("dcn", "ici"),
        shape=(2, 4),
        coords={
            i: (i // 4, i % 4) for i in range(8)
        },
    )
    hop = Instruction(
        name="cp.1", op="collective-permute",
        buffers=(Buffer("f32", (1024,)),), refs=frozenset(),
        op_name="", computation="main",
        source_target_pairs=((0, 1), (1, 2), (2, 3), (3, 0)),
    )
    ar = Instruction(
        name="ar.1", op="all-reduce",
        buffers=(Buffer("f32", (256,)),), refs=frozenset(),
        op_name="", computation="main",
        replica_groups=((0, 4), (1, 5), (2, 6), (3, 7)),
    )
    cols = [
        classify_instruction(hop, mesh),
        classify_instruction(ar, mesh),
    ]
    out = cost.predict_collectives(cols, mesh, dcn_axis="dcn")
    # hop: 4096 B within {ici} -> alpha 1us, beta 4096/100e9.
    # ar: 1024 B across {dcn} (group 2) -> alpha 2*1*10us, beta
    #     2*(1/2)*1024/25e9.
    assert out.n_collectives == 2
    assert out.alpha_s == pytest.approx(1e-6 + 2e-5, rel=1e-12)
    assert out.beta_s == pytest.approx(
        4096 / 100e9 + 1024 / 25e9, rel=1e-12
    )
    assert out.bytes_by_fabric == {"ici": 4096, "dcn": 1024}


def test_combo_cost_row_shape():
    """One cheap op-level combo through the real lower+classify+predict
    path (the costgate pre-gate's unit of work)."""
    from distributed_model_parallel_tpu.analysis.lint import Combo

    row = cost.combo_cost(Combo("cm_ag", 2))
    assert row["predicted_step_s"] > 0
    assert row["n_collectives"] >= 1
    assert set(row) >= {
        "predicted_step_s", "alpha_s", "beta_s", "n_collectives",
        "bytes_by_fabric",
    }


# ----------------------------------------------------------- costgate


def _ledger(rows):
    return make_ledger(rows, tolerance=0.05)


def test_costgate_regression_detected_and_named():
    ledger = _ledger({"ddp/S4/bucketed": {"predicted_step_s": 1e-3}})
    fails = gate_check(
        ledger, {"ddp/S4/bucketed": {"predicted_step_s": 1.2e-3}}
    )
    assert len(fails) == 1
    assert "ddp/S4/bucketed" in fails[0]
    assert "regressed" in fails[0]


def test_costgate_tolerance_boundary():
    ledger = _ledger({"x": {"predicted_step_s": 1e-3}})
    # Within tolerance (exactly +5%) passes; just past it fails.
    assert gate_check(ledger, {"x": {"predicted_step_s": 1.05e-3}}) \
        == []
    assert gate_check(ledger, {"x": {"predicted_step_s": 1.06e-3}})
    # Improvements always pass.
    assert gate_check(ledger, {"x": {"predicted_step_s": 0.5e-3}}) \
        == []


def test_costgate_missing_row_fails_for_new_combo():
    ledger = _ledger({"x": {"predicted_step_s": 1e-3}})
    fails = gate_check(
        ledger,
        {"x": {"predicted_step_s": 1e-3},
         "new/S2": {"predicted_step_s": 1e-3}},
    )
    assert len(fails) == 1 and "new/S2" in fails[0] \
        and "no ledger row" in fails[0]
    # The pre-gate's name check catches combos that were not lowered.
    fails = gate_check(
        ledger, {"x": {"predicted_step_s": 1e-3}},
        require_rows_for=["x", "unlowered/S8"],
    )
    assert len(fails) == 1 and "unlowered/S8" in fails[0]


def test_costgate_subset_update_refuses_drifted_constants(tmp_path):
    """A --filter/--pregate --update onto a ledger priced under
    different constants must refuse BEFORE lowering anything: merging
    would keep the un-lowered rows at the old physics while stamping
    the file with the new constants."""
    from distributed_model_parallel_tpu.observability import costgate

    ledger = _ledger({"x": {"predicted_step_s": 1e-3}})
    ledger["constants"]["alpha_hop_s"] = 123.0
    path = tmp_path / "ledger.json"
    path.write_text(json.dumps(ledger))
    rc = costgate.main([
        "--update", "--filter", "cm_ag/S2", "--ledger", str(path),
    ])
    assert rc == 2
    # The refusal left the drifted ledger untouched.
    assert json.loads(path.read_text()) == ledger


def test_costgate_constants_drift_fails():
    ledger = _ledger({"x": {"predicted_step_s": 1e-3}})
    ledger["constants"]["alpha_hop_s"] = 2e-6
    fails = gate_check(ledger, {"x": {"predicted_step_s": 1e-3}})
    assert len(fails) == 1 and "alpha_hop_s" in fails[0]


def test_committed_ledger_covers_the_full_matrix():
    """The acceptance pin: experiments/cost_ledger.json carries a row
    for EVERY combo in the hlolint matrix, under the current
    constants."""
    from distributed_model_parallel_tpu.analysis.lint import full_matrix
    from distributed_model_parallel_tpu.observability.costgate import (
        DEFAULT_LEDGER,
        load_ledger,
    )

    ledger = load_ledger(DEFAULT_LEDGER)
    assert gate_check(
        ledger, {}, require_rows_for=[c.name for c in full_matrix()]
    ) == []


# ------------------------------------------- trainer + serving smokes


def test_trainer_phase_spans_smoke(tmp_path, devices):
    """Trainer phase timing on the virtual mesh: one tiny epoch with a
    sharded async checkpoint must leave fetch/step/sync spans plus the
    checkpoint-blocked / snapshot / background-write trio."""
    import jax

    from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DataParallelEngine,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec,
        make_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD
    from distributed_model_parallel_tpu.training.trainer import (
        Trainer,
        TrainerConfig,
    )

    tracer = trace.Tracer(enabled=True)
    trace.set_tracer(tracer)
    reg = metrics.MetricsRegistry(enabled=True)
    metrics.set_metrics(reg)
    try:
        mesh = make_mesh(MeshSpec(data=2), devices=devices[:2])
        engine = DataParallelEngine(tiny_cnn(10), SGD(), mesh)
        rng = np.random.RandomState(0)
        batches = [
            (
                rng.rand(8, 8, 8, 3).astype(np.float32),
                rng.randint(0, 10, 8).astype(np.int32),
            )
            for _ in range(2)
        ]
        cfg = TrainerConfig(
            epochs=1, print_freq=1, save_best=False, save_last=True,
            checkpoint_format="sharded", async_save=True,
            checkpoint_dir=str(tmp_path), log_dir=str(tmp_path),
        )
        trainer = Trainer(engine, batches, None, cfg,
                          rng=jax.random.PRNGKey(0))
        trainer.fit()
        names = {
            e["name"] for e in tracer.to_chrome()["traceEvents"]
        }
        assert {
            "fetch", "step", "sync", "checkpoint_blocked",
            "ckpt_snapshot", "ckpt_background_write",
        } <= names
        # The metrics registry mirrors the phases as distributions
        # (tentpole wiring: step-time / fetch / checkpoint-blocked
        # histograms plus the checkpoint writer pair).
        exported = reg.to_json()
        assert {
            "train_fetch_s", "train_step_s",
            "train_checkpoint_blocked_s", "ckpt_snapshot_s",
            "ckpt_background_write_s",
        } <= set(exported["histograms"])
        assert reg.histogram("train_step_s").count == 2
        assert exported["counters"]["train_batches_total"] == 2
        # And the REAL CPU-mesh trace renders through obsreport: the
        # attribution covers the trainer+checkpoint phases, the
        # residual is finite, and the measured-vs-predicted row keys
        # on a live ledger combo (acceptance: the report pipeline
        # works on an actual run, not just the canned golden).
        from distributed_model_parallel_tpu.observability import (
            attribution,
            report,
        )
        from distributed_model_parallel_tpu.observability.costgate import (
            DEFAULT_LEDGER,
            load_ledger,
        )

        chrome = tracer.to_chrome()
        attr = attribution.attribute(chrome)
        assert {"fetch", "step", "sync", "checkpoint_blocked"} <= {
            p.name for p in attr.phases
        }
        assert 0.0 <= attr.residual_share < 1.0
        rendered = report.render_report(
            chrome, metrics=exported, ledger=load_ledger(DEFAULT_LEDGER),
            combos=["ddp/S4/dcn2/bucketed"],
        )
        assert "unattributed residual" in rendered
        assert "ddp/S4/dcn2/bucketed" in rendered
        assert "train_step_s" in rendered
    finally:
        trace.set_tracer(None)
        metrics.set_metrics(None)


def test_serving_telemetry_and_request_spans(devices):
    """Scheduler telemetry: goodput / mean occupancy in the report and
    the per-request queued/prefill/decode spans plus the per-step
    occupancy counter in the trace."""
    import jax

    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.serving.engine import (
        ServingEngine,
    )
    from distributed_model_parallel_tpu.serving.scheduler import (
        Request,
    )

    tracer = trace.Tracer(enabled=True)
    trace.set_tracer(tracer)
    reg = metrics.MetricsRegistry(enabled=True)
    metrics.set_metrics(reg)
    try:
        cfg = GPTConfig(
            vocab_size=32, dim=16, num_layers=1, num_heads=2,
            ffn_dim=32, max_position=16, dropout_rate=0.0,
        )
        eng = ServingEngine(
            cfg, None, layout="replicated", num_slots=2, max_len=16,
            prefill_len=4,
        )
        params = eng.init_params(jax.random.PRNGKey(0))
        rng = np.random.RandomState(0)
        reqs = [
            Request(rid=i, prompt=rng.randint(1, 32, size=3),
                    max_new_tokens=3)
            for i in range(3)
        ]
        sched = eng.run(params, reqs)
        rep = sched.latency_report()
        assert rep["requests"] == 3
        assert rep["decode_steps"] == len(sched.step_occupancy) > 0
        assert 0 < rep["mean_batch_occupancy"] <= 2
        assert 0 < rep["goodput"] <= 1
        # goodput IS occupancy over capacity (each active slot yields
        # one token per step).
        assert rep["goodput"] == pytest.approx(
            rep["mean_batch_occupancy"] / 2, abs=1e-3
        )
        events = tracer.to_chrome()["traceEvents"]
        names = {e["name"] for e in events}
        assert {
            "prefill", "decode_step", "queued", "decode",
            "batch_occupancy",
        } <= names
        # One queued+prefill+decode trio per finished request, each on
        # its own named track.
        assert sum(1 for e in events if e["name"] == "queued") == 3
        assert len({
            e["tid"] for e in events if e["name"] == "queued"
        }) == 3
        # Serving metrics wiring: per-request histograms through the
        # scheduler, per-call histograms through the engine, goodput /
        # occupancy as gauges, generated tokens as a counter.
        exported = reg.to_json()
        assert {
            "serve_queued_s", "serve_ttft_s", "serve_token_s",
            "serve_prefill_s", "serve_decode_step_s",
        } <= set(exported["histograms"])
        assert exported["histograms"]["serve_ttft_s"]["count"] == 3
        assert exported["histograms"]["serve_token_s"]["count"] == sum(
            len(f.tokens) - 1 for f in sched.finished
        )
        assert exported["gauges"]["serve_goodput"] == rep["goodput"]
        assert exported["counters"]["serve_tokens_total"] == sum(
            len(f.tokens) for f in sched.finished
        ) == rep["generated_tokens"]
    finally:
        trace.set_tracer(None)
        metrics.set_metrics(None)


def test_scheduler_request_spans_coherent_under_injected_clock():
    """The scheduler takes its lifecycle timestamps from the TRACER's
    clock (Tracer.now), so an injected clock yields a coherent trace:
    span ts/dur follow the fake clock exactly, never wall time."""
    from distributed_model_parallel_tpu.serving.scheduler import (
        Request,
        Scheduler,
    )

    clock = FakeClock()
    tracer = trace.Tracer(clock=clock, enabled=True)  # origin = 1.0
    trace.set_tracer(tracer)
    try:
        sched = Scheduler(num_slots=1, max_len=8)
        sched.submit(Request(rid="r", prompt=np.array([1, 2]),
                             max_new_tokens=1))          # t_submit 2.0
        seq = sched.admit()                              # t_admit 3.0
        seq.t_first_token = tracer.now()                 # 4.0
        seq.generated.append(7)
        sched.finish(seq.slot)                           # eviction 5.0
        spans = {
            e["name"]: e
            for e in tracer.to_chrome()["traceEvents"]
            if e["ph"] == "X"
        }
        assert spans["queued"]["ts"] == pytest.approx(1e6)   # 2.0-1.0
        assert spans["queued"]["dur"] == pytest.approx(1e6)
        assert spans["prefill"]["dur"] == pytest.approx(1e6)
        assert spans["decode"]["dur"] == pytest.approx(1e6)
        fin = sched.finished[0]
        assert fin.prefill_s == pytest.approx(2.0)  # submit->first tok
        assert fin.total_s == pytest.approx(3.0)
    finally:
        trace.set_tracer(None)


def test_serve_cli_trace_out_missing_dir_fails_fast():
    """--trace-out with a nonexistent directory exits BEFORE any
    engine compiles, naming the directory."""
    from distributed_model_parallel_tpu.cli import serve

    with pytest.raises(SystemExit) as exc:
        serve.main([
            "--trace-out", "/no/such/dir/anywhere/trace.json",
            "--num-requests", "1",
        ])
    assert "does not exist" in str(exc.value)


def test_serve_cli_metrics_out_missing_dir_fails_fast():
    """--metrics-out shares --trace-out's fail-fast contract: a
    mistyped directory must not surface as a lost export after the
    whole run."""
    from distributed_model_parallel_tpu.cli import serve

    with pytest.raises(SystemExit) as exc:
        serve.main([
            "--metrics-out", "/no/such/dir/anywhere/metrics.json",
            "--num-requests", "1",
        ])
    assert "does not exist" in str(exc.value)


def test_progress_print_never_measures_its_own_readback_stall(
    monkeypatch, devices,
):
    """The progress-print fence fix, regression-pinned with an injected
    slow clock: every `jax.device_get` of the JUST-dispatched group's
    metrics advances the fake clock by 10 s (the readback stall of
    fencing in-flight compute). Because the progress print reads the
    PREVIOUS group's metrics through the one-deep snapshot seam — and
    the step-time sample closes BEFORE the print's fetch — at most the
    first print's no-predecessor fallback can land a stall in the
    train_step_s histogram. The pre-fix loop (fetching the current
    group at every print) puts one in every window after the first."""
    import jax

    from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DataParallelEngine,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec,
        make_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD
    from distributed_model_parallel_tpu.training.trainer import (
        Trainer,
        TrainerConfig,
    )

    class TickClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            self.t += 1e-3
            return self.t

    clock = TickClock()
    trace.set_tracer(trace.Tracer(clock=clock))  # tracing stays OFF
    reg = metrics.MetricsRegistry(enabled=True)
    metrics.set_metrics(reg)
    try:
        mesh = make_mesh(MeshSpec(data=2), devices=devices[:2])
        engine = DataParallelEngine(tiny_cnn(10), SGD(), mesh)
        rng = np.random.RandomState(0)
        batches = [
            (
                rng.rand(8, 8, 8, 3).astype(np.float32),
                rng.randint(0, 10, 8).astype(np.int32),
            )
            for _ in range(4)
        ]
        cfg = TrainerConfig(
            epochs=1, print_freq=1, save_best=False,
        )
        trainer = Trainer(engine, batches, None, cfg,
                          rng=jax.random.PRNGKey(0))

        latest = []
        orig_step = engine.train_step

        def recording_step(state, *a):
            state, m = orig_step(state, *a)
            latest.append(m)
            return state, m

        monkeypatch.setattr(engine, "train_step", recording_step)
        orig_get = jax.device_get

        def slow_get(tree):
            # Fetching the newest dispatch's metrics = fencing the
            # in-flight compute: charge the injected stall. Anything
            # older already finished behind the newer dispatch.
            if latest and tree is latest[-1]:
                clock.t += 10.0
            return orig_get(tree)

        monkeypatch.setattr(jax, "device_get", slow_get)
        trainer.train_epoch(0)
        hist = reg.histogram("train_step_s")
        assert hist is not None and hist.count == 4
        samples = hist._samples
        stalled = sum(1 for s in samples if s > 5.0)
        assert stalled <= 1, (
            f"step-time histogram measured its own readback stall: "
            f"{samples}"
        )
        # And the fix costs nothing at the tail: the LAST window is
        # always stall-free.
        assert samples[-1] < 5.0
    finally:
        trace.set_tracer(None)
        metrics.set_metrics(None)
