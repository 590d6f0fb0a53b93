"""Observability subsystem (INTERNALS.md §13): the span tracer's
nesting/export contract against a committed Chrome-trace golden file
(deterministic clock injected — no wall time in any assertion), every
documented trace event held to a run of the program that emits it, the
Trainer's and the scheduler's telemetry on the virtual mesh, and the
package's layering as a source scan."""

import dataclasses
import functools
import json
import os
import tempfile

import jax
import numpy as np
import pytest

from distributed_model_parallel_tpu.models.gpt import GPTConfig
from distributed_model_parallel_tpu.observability import metrics, trace
from distributed_model_parallel_tpu.serving.engine import ServingEngine
from distributed_model_parallel_tpu.serving.scheduler import Request

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


# --------------------------------- tiny runs with both instruments on
#
# What the program emits is read from its own runs: each run below is
# made once per process with a fresh tracer and registry on, and
# `test_documented_span_is_emitted` here and
# `test_metrics.py::test_documented_metric_is_emitted` hold every name
# of the two registries to the union of what the runs left behind.
# (`scan_emitted_names` holds the other direction: emitted, so
# documented.)


@dataclasses.dataclass(frozen=True)
class Observed:
    events: list   # the tracer's Chrome events
    metrics: dict  # the registry's JSON export
    result: object  # what the run returned

    def names(self) -> set:
        return {e["name"] for e in self.events}


def observed(run) -> Observed:
    tracer = trace.Tracer(enabled=True)
    reg = metrics.MetricsRegistry(enabled=True)
    trace.set_tracer(tracer)
    metrics.set_metrics(reg)
    try:
        result = run()
    finally:
        trace.set_tracer(None)
        metrics.set_metrics(None)
    return Observed(tracer.to_chrome()["traceEvents"], reg.to_json(),
                    result)


def _fit(engine, batches, **config):
    from distributed_model_parallel_tpu.training.trainer import (
        Trainer,
        TrainerConfig,
    )

    with tempfile.TemporaryDirectory() as tmp:
        cfg = TrainerConfig(
            epochs=1, print_freq=1, save_best=False,
            checkpoint_dir=tmp, log_dir=tmp, **config,
        )
        trainer = Trainer(engine, batches, None, cfg,
                          rng=jax.random.PRNGKey(0))
        return observed(trainer.fit)


@functools.cache
def trainer_epoch() -> Observed:
    """Two batches through the Trainer, then a sharded async save."""
    from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn
    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DataParallelEngine,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec,
        make_mesh,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    mesh = make_mesh(MeshSpec(data=2), devices=jax.devices()[:2])
    engine = DataParallelEngine(tiny_cnn(10), SGD(), mesh)
    rng = np.random.RandomState(0)
    batches = [
        (
            rng.rand(8, 8, 8, 3).astype(np.float32),
            rng.randint(0, 10, 8).astype(np.int32),
        )
        for _ in range(2)
    ]
    return _fit(engine, batches, save_last=True,
                checkpoint_format="sharded", async_save=True)


def _lm_batches(vocab, batch, seq_len):
    ids = np.random.RandomState(0).randint(
        1, vocab, size=(batch, seq_len)
    ).astype(np.int32)
    return [(ids, ids)] * 2


@functools.cache
def fsdp_plan_epoch() -> Observed:
    """A plan whose sequence is whole on a chip: the engine says which
    local attention its step holds."""
    from distributed_model_parallel_tpu.parallel.plan import (
        build_plan_engine,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    engine = build_plan_engine(
        SERVE_CFG, SGD(), "fsdp2", devices=jax.devices()[:2],
        min_shard_elems=64,
    )
    return _fit(engine, _lm_batches(SERVE_CFG.vocab_size, 4, 16))


@functools.cache
def held_experts_epoch() -> Observed:
    """The family whose sparse layer holds a range of the experts, two
    layers of it, behind the engine `cli.lm --model-config` builds."""
    from test_kimi_linear import TOY, engine_for

    from distributed_model_parallel_tpu.models import kimi_linear

    cfg = kimi_linear.config_from_dict({**TOY, "num_hidden_layers": 2})
    return _fit(engine_for(cfg), _lm_batches(cfg.vocab_size, 2, 32))


SERVE_CFG = GPTConfig(
    vocab_size=32, dim=16, num_layers=1, num_heads=2, ffn_dim=32,
    max_position=16, dropout_rate=0.0,
)
PAGED = dict(num_slots=2, max_len=16, prefill_len=8, page_size=4,
             prefill_chunk=4)


def _drain(eng, params, reqs, **kw) -> Observed:
    def run():
        sched = eng.run(params, reqs, **kw)
        sched.latency_report()  # the goodput gauge is set here
        return sched

    return observed(run)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(
        1, SERVE_CFG.vocab_size, size=n
    ).astype(np.int32)


@functools.cache
def contiguous_drain() -> Observed:
    eng = ServingEngine(
        SERVE_CFG, None, layout="replicated", num_slots=2, max_len=16,
        prefill_len=4,
    )
    params = eng.init_params(jax.random.PRNGKey(0))
    reqs = [
        Request(rid=i, prompt=_prompt(i, 3), max_new_tokens=3)
        for i in range(3)
    ]
    return _drain(eng, params, reqs)


@functools.cache
def paged_drain() -> Observed:
    """Chunked prefill over pages with the prefix cache: more requests
    than slots, and the third repeats the first's prompt."""
    eng = ServingEngine(SERVE_CFG, prefix_cache=True, **PAGED)
    params = eng.init_params(jax.random.PRNGKey(0))
    seven = _prompt(1, 7)
    reqs = [
        Request(rid="a", prompt=seven, max_new_tokens=4),
        Request(rid="b", prompt=_prompt(2, 3), max_new_tokens=3),
        Request(rid="c", prompt=seven, max_new_tokens=3),
    ]
    return _drain(eng, params, reqs)


@functools.cache
def speculative_drain() -> Observed:
    target = ServingEngine(SERVE_CFG, speculative_k=2, **PAGED)
    draft = ServingEngine(SERVE_CFG, **PAGED)
    params = target.init_params(jax.random.PRNGKey(0))
    dparams = draft.init_params(jax.random.PRNGKey(7))
    reqs = [
        Request(rid=i, prompt=_prompt(i, 3 + i), max_new_tokens=4)
        for i in range(3)
    ]
    return _drain(target, params, reqs, draft=draft,
                  draft_params=dparams)


@pytest.mark.parametrize("name", sorted(metrics.TRACE_EVENT_NAMES))
def test_documented_span_is_emitted(name):
    """No dead name in the registry of trace events: a run of the
    program leaves each one behind."""
    runs = (trainer_epoch, contiguous_drain, paged_drain,
            speculative_drain)
    assert any(name in run().names() for run in runs)


def test_trainer_epoch_counts_its_batches_and_its_save():
    """The registry mirrors the Trainer's phases as distributions: one
    sample a batch, one save that held the loop, its two halves."""
    hist = trainer_epoch().metrics["histograms"]
    assert hist["train_step_s"]["count"] == 2
    assert hist["train_fetch_s"]["count"] == 2
    assert hist["train_checkpoint_blocked_s"]["count"] == 1
    assert hist["ckpt_snapshot_s"]["count"] == 1
    assert hist["ckpt_background_write_s"]["count"] >= 1
    assert trainer_epoch().metrics["counters"]["train_batches_total"] == 2


def test_serving_telemetry_and_request_spans():
    """Scheduler telemetry: goodput / mean occupancy in the report and
    the per-request queued/prefill/decode spans in the trace."""
    run = contiguous_drain()
    sched = run.result
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
    # One queued+prefill+decode trio per finished request, each on
    # its own named track.
    queued = [e for e in run.events if e["name"] == "queued"]
    assert len(queued) == len({e["tid"] for e in queued}) == 3
    # Per-request histograms through the scheduler, goodput as a
    # gauge, generated tokens as a counter.
    exported = run.metrics
    assert exported["histograms"]["serve_ttft_s"]["count"] == 3
    assert exported["histograms"]["serve_token_s"]["count"] == sum(
        len(f.tokens) - 1 for f in sched.finished
    )
    assert exported["gauges"]["serve_goodput"] == rep["goodput"]
    assert exported["counters"]["serve_tokens_total"] == sum(
        len(f.tokens) for f in sched.finished
    ) == rep["generated_tokens"]


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


# ------------------------------------------------------------ layering
#
# cli -> {training, serving} -> parallel -> {models, ops} -> runtime;
# observability (trace, metrics) imports nothing of the package and is
# imported by any; analysis is a test-time tool that may import
# engines; the benchmark sits outside and reads the tracer.

PACKAGE = "distributed_model_parallel_tpu"
RUNTIME_LAYERS = (
    "runtime", "ops", "models", "data", "parallel", "training",
    "checkpointing", "serving", "observability",
)
# What a runtime layer never imports: the layers above it, the
# benchmark, and the modules that priced, gated, fitted, reported or
# tuned speed (PR 32 deleted them).
ABOVE_THE_RUNTIME = (
    f"{PACKAGE}.analysis", f"{PACKAGE}.cli", f"{PACKAGE}.tuning",
    "benchmark", "bench",
) + tuple(
    f"{PACKAGE}.observability.{gone}"
    for gone in ("cost", "costgate", "calibrate", "attribution", "report")
)


def imports_of(subpackage):
    """{imported module: [file:line, ...]} over the sub-package's
    source, imports inside functions included."""
    import ast

    root = os.path.join(
        os.path.dirname(os.path.dirname(__file__)), PACKAGE, subpackage
    )
    found = {}
    for dirpath, _dirs, files in os.walk(root):
        for fn in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    modules = [a.name for a in node.names]
                elif isinstance(node, ast.ImportFrom):
                    assert node.level == 0, f"{path}: relative import"
                    # `from pkg import sub` names pkg.sub as well
                    modules = [node.module] + [
                        f"{node.module}.{a.name}" for a in node.names
                    ]
                else:
                    continue
                for module in modules:
                    found.setdefault(module, []).append(
                        f"{os.path.relpath(path, root)}:{node.lineno}"
                    )
    return found


def _under(module, prefixes):
    return any(module == p or module.startswith(p + ".") for p in prefixes)


@pytest.mark.parametrize("subpackage", RUNTIME_LAYERS + ("analysis",))
def test_package_imports_point_one_way(subpackage):
    found = imports_of(subpackage)
    assert found, "the scan read no import at all"
    own = (f"{PACKAGE}.observability",)

    def refused(module):
        if subpackage == "analysis":
            return _under(module, (f"{PACKAGE}.cli",))
        if subpackage == "observability" and not _under(module, own):
            return _under(module, (PACKAGE,) + ABOVE_THE_RUNTIME)
        return _under(module, ABOVE_THE_RUNTIME)

    assert {m: at for m, at in found.items() if refused(m)} == {}
