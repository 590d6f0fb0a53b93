"""Benchmark entry point — measures on the accelerator, in this process.

Headline metric: MobileNetV2 CIFAR-10 data-parallel training throughput
(images/sec across the whole mesh) in bf16, the exact workload behind the
reference's only published performance table: `nn.DataParallel`, batch 512,
0.396 s/batch on 4 GPUs = 1292.9 images/sec (`Readme.md:283-287`,
SURVEY.md §6). `vs_baseline` is our images/sec divided by that number.
The line also carries an MFU estimate (XLA cost-analysis FLOPs / step time
/ chip peak), the f32 throughput, and explicit model/batch/dtype and
platform/device_kind fields.

`python bench.py` needs a chip: where JAX finds none it exits non-zero
with one line on stderr and prints no metric line — a CPU number is never
written under a device metric's name. One process holds the chip, so
every mode runs in this process; a mode that raises exits non-zero.

`--scaling` and the `--*-microbench` modes print a table instead of the
headline line. They default to the virtual CPU mesh
(`--scaling-platform cpu`), where their numbers are counts and
structure checks, not device speed; `--scaling-platform default` runs
them on the chips JAX finds.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

# Reference: DP 0.396 s/batch @ global batch 512 on 4 GPUs (Readme.md:283-287).
BASELINE_IMG_PER_SEC = 512 / 0.396

METRIC = "mobilenetv2_cifar10_dp_train_throughput"

# Peak bf16 matmul TFLOP/s per chip by TPU generation (public numbers);
# MFU is measured FLOP/s divided by this. A device_kind that matches no
# key is an error, never a default.
PEAK_BF16_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}


def peak_bf16_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, tflops in sorted(
        PEAK_BF16_TFLOPS.items(), key=lambda kv: -len(kv[0])
    ):
        if key in kind:
            return tflops * 1e12
    raise ValueError(
        f"no bf16 peak known for device_kind {device_kind!r}; add it to "
        "PEAK_BF16_TFLOPS with its source before reporting an MFU"
    )


# ---- run-metadata header (self-describing trajectory files): every
# emitted BENCH/MULTICHIP JSON carries the git sha, jax version, mesh
# axes (once a mode built one), and backend platform it was measured
# under, so a BENCH_r*.json is attributable without the round's logs.
_RUN_META: dict | None = None
_MESH_AXES: dict | None = None


def _note_mesh(mesh) -> None:
    """Record the measuring mode's mesh axes for the run_meta header."""
    global _MESH_AXES
    try:
        _MESH_AXES = {
            str(a): int(mesh.shape[a]) for a in mesh.axis_names
        }
    except Exception:  # noqa: BLE001 — header is best-effort
        pass


def _run_meta(**extra) -> dict:
    global _RUN_META
    if _RUN_META is None:
        meta = {}
        try:
            sha = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=5,
            ).stdout.strip()
            meta["git_sha"] = sha or None
        except Exception:  # noqa: BLE001 — header is best-effort
            meta["git_sha"] = None
        try:
            # Version only — importing jax.version starts no backend.
            from jax import version as _jax_version

            meta["jax_version"] = _jax_version.__version__
        except Exception:  # noqa: BLE001
            meta["jax_version"] = None
        _RUN_META = meta
    out = dict(_RUN_META)
    if _MESH_AXES is not None:
        out["mesh_axes"] = _MESH_AXES
    out.update({k: v for k, v in extra.items() if v is not None})
    return out


# ---- cost-engine column: where the committed ledger
# (experiments/cost_ledger.json, tools/costgate) has a row for the
# hlolint-matrix combo matching a sweep row's shape, the row carries
# that combo's predicted step time. The ledger prices the LINT-sized
# model on the modeled TPU fabrics — a structural reference column, not
# a forecast of the CPU-measured milliseconds beside it.
_LEDGER: dict | None = None


def _ledger_predicted_ms(combo_name: str):
    """The ledger combo's predicted step time in ms (float), or None
    when the ledger or the row is absent."""
    global _LEDGER
    if _LEDGER is None:
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "experiments", "cost_ledger.json",
        )
        try:
            with open(path) as f:
                _LEDGER = json.load(f).get("combos", {})
        except Exception:  # noqa: BLE001 — column is best-effort
            _LEDGER = {}
    row = _LEDGER.get(combo_name)
    if row is None:
        return None
    return round(float(row["predicted_step_s"]) * 1e3, 6)


def _with_predicted(row: dict, *combo_names: str,
                    measured_key: str = None) -> dict:
    """Attach the first ledger hit among `combo_names` (the matrix
    ships some shapes only in a model/overlap variant, so callers pass
    the exact twin first and its variants as fallbacks). When
    `measured_key` names the row's measured-ms column, also attach
    `delta_pct` (measured vs predicted, +slower) so prediction drift
    is visible in every committed BENCH artifact and per-leg partial
    line — the drift `tools/obsreport`/`calibrate.py` reconcile."""
    for name in combo_names:
        ms = _ledger_predicted_ms(name)
        if ms is not None:
            row["predicted_ms"] = ms
            row["predicted_combo"] = name
            measured = row.get(measured_key) if measured_key else None
            if measured is not None and ms > 0:
                row["delta_pct"] = round(
                    (float(measured) - ms) / ms * 100.0, 1
                )
            return row
    return row


def emit(value: float, vs_baseline: float, **extra) -> None:
    print(json.dumps({
        "metric": METRIC,
        "value": round(value, 1),
        "unit": "images/sec",
        "vs_baseline": round(vs_baseline, 3),
        "run_meta": _run_meta(platform=extra.get("platform")),
        **extra,
    }), flush=True)


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------ measurements


def _fake_batch(batch: int, seed: int = 0, hw: int = 32):
    import numpy as np

    rng = np.random.RandomState(seed)
    images = rng.rand(batch, hw, hw, 3).astype(np.float32)
    labels = rng.randint(0, 10, size=(batch,)).astype(np.int32)
    return images, labels


def _sync(state) -> int:
    """The timing barrier: fetch a value that depends on every queued
    step. On the v5e `jax.block_until_ready` is an equally honest
    barrier (a chained 8192^3 bf16 matmul timed 185.9 TFLOP/s with it
    and 185.5 with a value fetch — chip_smoke.py's barrier leg re-checks
    this on every run); the fetch is kept here because the step counter
    is what these loops have in hand."""
    import jax

    return int(jax.device_get(state.step))


def _aot_step(engine, state, images, labels, lr):
    """AOT-compile the train step ONCE and return (step_fn, flops).

    Using the same compiled executable for cost analysis and the timing
    loop avoids the double compile that `lower().compile()` + a jit call
    would cost (the AOT executable does not populate the jit dispatch
    cache)."""
    compiled = engine.train_step.lower(state, images, labels, lr).compile()
    flops = float(compiled.cost_analysis()["flops"])
    return (lambda s: compiled(s, images, labels, lr)[0]), flops


def _bench_models():
    """Single registry: name -> (builder, input height/width). resnet50
    at 224 is the BASELINE.json north-star workload (ResNet-50
    images/sec/chip)."""
    from distributed_model_parallel_tpu.models.mobilenetv2 import mobilenet_v2
    from distributed_model_parallel_tpu.models.resnet import resnet50
    from distributed_model_parallel_tpu.models.tinycnn import tiny_cnn

    return {
        "mobilenetv2": (lambda: mobilenet_v2(10), 32),
        "tinycnn": (lambda: tiny_cnn(10), 32),
        "resnet50": (lambda: resnet50(1000), 224),
    }


def _measure(model_name: str, batch: int, dtype_name: str,
             warmup: int, iters: int):
    """One throughput measurement on the already-initialized backend.
    Returns dict with img/sec and (for the bf16 run) flops/step."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.parallel.data_parallel import (
        DataParallelEngine,
    )
    from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
    from distributed_model_parallel_tpu.training.optim import SGD

    builder, hw = _bench_models()[model_name]
    cdt = {"bfloat16": jnp.bfloat16, "float32": None}[dtype_name]
    mesh = make_mesh(MeshSpec(data=-1))
    _note_mesh(mesh)
    engine = DataParallelEngine(
        model=builder(), optimizer=SGD(), mesh=mesh, compute_dtype=cdt,
    )
    state = engine.init_state(jax.random.PRNGKey(0))
    images, labels = engine.shard_batch(*_fake_batch(batch, hw=hw))
    lr = jnp.float32(0.2)

    log(f"compiling {model_name} batch={batch} dtype={dtype_name} ...")
    t0 = time.perf_counter()
    step, flops = _aot_step(engine, state, images, labels, lr)
    for _ in range(warmup):
        state = step(state)
    _sync(state)
    log(f"compile+warmup took {time.perf_counter() - t0:.1f}s; measuring")
    # Adaptive iteration count: size the measurement window to ~3s so a
    # few-ms TPU step gets a stable average (and the one value-fetch
    # roundtrip in _sync amortizes away), not a noise sample.
    t0 = time.perf_counter()
    for _ in range(iters):
        state = step(state)
    _sync(state)
    dt = time.perf_counter() - t0
    if dt < 1.0:
        sec0 = dt / iters
        iters = min(int(iters * 3.0 / dt), 3000)
        log(f"fast step ({sec0:.5f}s); re-measuring with {iters} iters")
        t0 = time.perf_counter()
        for _ in range(iters):
            state = step(state)
        _sync(state)
        dt = time.perf_counter() - t0
    return {
        "img_per_sec": batch * iters / dt,
        "sec_per_step": dt / iters,
        "flops_per_step": flops,
    }


class NoAcceleratorError(RuntimeError):
    """JAX found no accelerator; the headline is a device metric."""


def run_headline() -> None:
    """Measure the headline (MobileNetV2, batch 512, bf16 then f32) on
    the chips JAX finds, printing the metric line after every completed
    leg (the last line is the result). Raises NoAcceleratorError before
    any work on a CPU-only JAX."""
    model_name, batch, dtypes = "mobilenetv2", 512, ("bfloat16", "float32")
    t0 = time.perf_counter()
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    device_kind = devs[0].device_kind
    n_chips = len(devs)
    if platform == "cpu":
        raise NoAcceleratorError(
            f"no accelerator: JAX found {n_chips} cpu device(s); "
            f"{METRIC} is measured on a chip only"
        )
    log(f"backend up in {time.perf_counter() - t0:.1f}s: "
        f"{n_chips}x {device_kind} ({platform})")
    peak = peak_bf16_flops(device_kind)

    def mfu_of(r):
        return round(
            r["flops_per_step"] / r["sec_per_step"] / (n_chips * peak), 4
        )

    # Re-emit the headline line after EVERY completed dtype leg, so a
    # time-limit kill mid-run keeps the legs that already ran. Non-final
    # legs carry "partial": true.
    results = {}
    extra = {}
    head_dtype = dtypes[0]
    for idx, dtype_name in enumerate(dtypes):
        results[dtype_name] = _measure(
            model_name, batch, dtype_name, warmup=5, iters=30
        )
        log(f"{dtype_name}: {results[dtype_name]['img_per_sec']:.1f} img/s")
        head = results[head_dtype]
        extra = {
            "platform": platform,
            "device_kind": device_kind,
            "n_chips": n_chips,
            "model": model_name,
            "batch": batch,
            "dtype": head_dtype,
            "sec_per_step": round(head["sec_per_step"], 4),
            "mfu": mfu_of(head),
            "flops_per_step": head["flops_per_step"],
        }
        for other in dtypes[1:idx + 1]:
            extra[f"{other}_img_per_sec"] = round(
                results[other]["img_per_sec"], 1
            )
        if idx < len(dtypes) - 1:
            extra["partial"] = True
        emit(head["img_per_sec"],
             head["img_per_sec"] / BASELINE_IMG_PER_SEC, **extra)
    # (the final loop iteration left `head`/`extra` at their complete,
    # non-partial values — the north-star extras below extend them)

    # North-star secondary metric (BASELINE.json): ResNet-50
    # images/sec/chip at 224², bf16. Re-emitted as an UPDATED line.
    log("north-star extra: resnet50 @ 224, bf16 ...")
    rn = _measure("resnet50", 256, "bfloat16", warmup=3, iters=20)
    extra.update({
        "resnet50_img_per_sec_per_chip": round(
            rn["img_per_sec"] / n_chips, 1
        ),
        "resnet50_batch": 256,
        "resnet50_mfu": mfu_of(rn),
    })
    emit(head["img_per_sec"],
         head["img_per_sec"] / BASELINE_IMG_PER_SEC, **extra)

    # End-to-end extra: the FULL train loop (Trainer -> IndexLoader
    # -> device-resident cache -> fused k-step dispatch), steady
    # state. Emitted as another update.
    log("e2e extra: device-cache + steps-per-dispatch loop ...")
    extra.update(_measure_e2e_loop(batch))
    emit(head["img_per_sec"],
         head["img_per_sec"] / BASELINE_IMG_PER_SEC, **extra)


def _measure_e2e_loop(batch: int, model_name: str = "mobilenetv2",
                      n_examples: int = 50_000,
                      steps_per_dispatch: int = 16) -> dict:
    """Steady-state s/batch of the real training loop under the fast
    input path (device cache + fused dispatch), bf16. Parameterized so
    the CPU test harness can drive it with tinycnn-sized work."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.data.datasets import (
        CIFAR10_MEAN,
        CIFAR10_STD,
        synthetic,
    )
    from distributed_model_parallel_tpu.data.device_cache import (
        DeviceDatasetCache,
        IndexLoader,
    )
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

    builder, hw = _bench_models()[model_name]
    mesh = make_mesh(MeshSpec(data=-1))
    train_ds = synthetic(n_examples, hw, 10, seed=1)
    # No val loader in this benchmark: a single-dataset cache suffices
    # (combined_cache exists for the train+val CLI contract).
    tf = DeviceDatasetCache(
        train_ds, mesh, augment=True,
        mean=CIFAR10_MEAN, std=CIFAR10_STD,
    ).transform()
    engine = DataParallelEngine(
        builder(), SGD(momentum=0.9), mesh,
        compute_dtype=jnp.bfloat16, input_transform=tf,
    )
    train = IndexLoader(train_ds, batch_size=batch, shuffle=True)
    cfg = TrainerConfig(
        epochs=3, base_lr=0.02, t_max=3, warmup_period=1, print_freq=0,
        save_best=False, steps_per_dispatch=steps_per_dispatch,
    )
    trainer = Trainer(engine, train, None, cfg,
                      rng=jax.random.PRNGKey(0))
    out = trainer.fit()
    last = out["history"][-1]["train"]
    return {
        "e2e_cache_sec_per_batch": round(last["batch_time"], 4),
        "e2e_cache_img_per_sec": round(batch / last["batch_time"], 1),
        "e2e_steps_per_dispatch": steps_per_dispatch,
    }


def run_scaling(max_devices: int, model_name: str = "tinycnn",
                platform: str = "cpu") -> None:
    """Weak-scaling sweep over the 'data' axis: images/sec/chip and
    efficiency vs N=1 (BASELINE.json north-star shape). Per-chip batch
    is held constant (weak scaling). platform='cpu' (default) uses
    virtual CPU devices (the CI harness, tinycnn-sized);
    platform='default' sweeps the chips JAX finds —
    pair with model_name='resnet50' for the north-star measurement on a
    real multi-chip slice."""
    if max_devices < 1:
        raise ValueError(f"--max-devices must be >= 1, got {max_devices}")
    if platform == "cpu":
        from distributed_model_parallel_tpu.runtime.platform import force_cpu

        force_cpu(max_devices)

    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.parallel.data_parallel import DDPEngine
    from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
    from distributed_model_parallel_tpu.training.optim import SGD

    builder, hw = _bench_models()[model_name]
    per_chip_batch = 64
    sizes = []
    n = 1
    while n <= max_devices:
        sizes.append(n)
        n *= 2
    if sizes[-1] != max_devices:
        sizes.append(max_devices)  # non-power-of-two cap still measured

    devices = jax.devices("cpu") if platform == "cpu" else jax.devices()
    sizes = [n for n in sizes if n <= len(devices)]
    rows = []
    for n in sizes:
        mesh = make_mesh(MeshSpec(data=n), devices=devices[:n])
        _note_mesh(mesh)
        engine = DDPEngine(model=builder(), optimizer=SGD(), mesh=mesh)
        state = engine.init_state(jax.random.PRNGKey(0))
        batch = per_chip_batch * n
        images, labels = engine.shard_batch(*_fake_batch(batch, hw=hw))
        lr = jnp.float32(0.1)
        for _ in range(2):
            state, _ = engine.train_step(state, images, labels, lr)
        _sync(state)
        iters = 10
        t0 = time.perf_counter()
        for _ in range(iters):
            state, _ = engine.train_step(state, images, labels, lr)
        _sync(state)
        dt = time.perf_counter() - t0
        per_chip = batch * iters / dt / n
        rows.append(_with_predicted(
            {"chips": n, "img_per_sec_per_chip": round(per_chip, 1)},
            f"ddp/S{n}/monolithic",
        ))
        # Per-leg partial line: a time-limit kill mid-sweep keeps the
        # sizes that already measured.
        print(json.dumps({"leg": rows[-1], "partial": True}), flush=True)
    base = rows[0]["img_per_sec_per_chip"]
    for r in rows:
        r["weak_scaling_efficiency"] = round(
            r["img_per_sec_per_chip"] / base, 3
        )
    out = {
        "scaling": rows,
        "run_meta": _run_meta(platform=jax.devices()[0].platform),
    }
    if jax.devices()[0].platform == "cpu":
        out["note"] = (
            "virtual CPU devices share one host core, so per-chip "
            "throughput necessarily drops ~1/N here; the harness is "
            "meaningful on real chips, where each mesh slot has its own "
            "silicon"
        )
    print(json.dumps(out, indent=2))


def _bench_plan(plan_path, families, sweep):
    """(knobs, combo name) from a tuner plan.json (`tuning/plan.py`),
    or (None, None) — the microbench modes time the tuned
    configuration as an extra row next to their default-knob rows.
    The plan's engine family must match the sweep: a cross-family
    plan's knobs would silently default-fill and the committed BENCH
    artifact would label an unrelated timing as 'tuned'."""
    if not plan_path:
        return None, None
    from distributed_model_parallel_tpu.tuning.plan import load_plan

    plan = load_plan(plan_path)
    family = plan["cell"]["family"]
    if family not in families:
        raise SystemExit(
            f"--plan {plan_path}: plan cell.family is {family!r} but "
            f"the {sweep} sweep times the "
            f"{'/'.join(families)} famil"
            f"{'ies' if len(families) > 1 else 'y'} — pass the "
            "matching microbench (or the matching plan)"
        )
    return plan["knobs"], plan["combo"]


def _tuned_row(axis_size: int, knobs, combo, tuned_ms: float,
               default_ms: float, default_leg: str) -> dict:
    """The tuned extra row: `tuned_vs_default_pct` > 0 means the tuned
    configuration beat the table's default-knob leg."""
    return {
        "axis_size": axis_size,
        "tuned": True,
        "plan_combo": combo,
        "knobs": dict(knobs),
        "tuned_ms": round(tuned_ms, 3),
        "default_leg": default_leg,
        "default_ms": default_ms,
        "tuned_vs_default_pct": round(
            (default_ms - tuned_ms) / max(default_ms, 1e-9) * 100.0, 2
        ),
    }


def run_plan_bench(max_devices: int, platform: str = "cpu",
                   plan_path=None) -> None:
    """Composed-ParallelPlan microbench (parallel/plan.py, ISSUE
    19/20): one tiny-GPT train step per mesh factorization of the
    device world — the pure-data plan (the table's default leg)
    against the pp2/sp2 composed factorizations, plus the SCHEDULE
    column: gpipe vs 1f1b vs int2 twins of one pp2 plan at fixed
    M=4, the SAME spec strings the training CLI's `--plan` takes,
    all through build_plan_engine.
    Every row carries the alpha-beta prediction for ITS factorization
    (`cost.composed_plan_step_s` — wire + seq-ring + fused-psum legs)
    and, when the committed ledger has the matching plan/S combo, the
    ledger column + drift delta. Emits one partial JSON line per
    completed spec (a kill mid-sweep keeps the finished legs), then
    the table. `--plan PLAN.json` (a plan-family tuner artifact,
    `--plan auto --auto-tune search`'s output) adds the tuned row
    with tuned_vs_default_pct against the pure-data leg."""
    if max_devices < 4:
        raise ValueError(
            f"--max-devices must be >= 4 for a composed plan, "
            f"got {max_devices}"
        )
    if platform == "cpu":
        from distributed_model_parallel_tpu.runtime.platform import force_cpu

        force_cpu(max_devices)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.observability import cost
    from distributed_model_parallel_tpu.parallel.plan import (
        build_plan_engine,
        parse_plan,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    knobs, combo = _bench_plan(plan_path, ("plan",), "composed-plan")

    devices = jax.devices("cpu") if platform == "cpu" else jax.devices()
    size = 1
    while size * 2 <= min(max_devices, len(devices)):
        size *= 2
    if size < 4:
        raise ValueError(
            f"composed plans need >= 4 devices, {len(devices)} present"
        )

    cfg = GPTConfig(
        vocab_size=61, dim=16, num_layers=4, num_heads=2, ffn_dim=32,
        max_position=16, dropout_rate=0.0,
    )
    batch = 2 * size  # divides dp*M for every factorization below
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 61, size=(batch, 16)).astype(np.int32)

    def _time_spec(spec: str, m: int = None) -> dict:
        plan = parse_plan(spec)
        engine = build_plan_engine(
            cfg, SGD(), plan, devices=devices[:size], donate=False,
            num_microbatches=m,
        )
        state = engine.init_state(jax.random.PRNGKey(0))
        sids, stg = engine.shard_batch(ids)
        lr = jnp.float32(0.05)
        for _ in range(2):
            state, _ = engine.train_step(state, sids, stg, lr)
        _sync(state)
        iters = 10
        t0 = time.perf_counter()
        for _ in range(iters):
            state, _ = engine.train_step(state, sids, stg, lr)
        _sync(state)
        step_ms = (time.perf_counter() - t0) / iters * 1e3
        grad_bytes = 4 * sum(
            int(np.prod(l.shape))
            for l in jax.tree_util.tree_leaves(
                engine.to_canonical(state.params)
            )
        )
        # Schedule-aware microbatch count: the engine defaults M to
        # pp*V chunks for the interleaved schedule, pp otherwise.
        n_mb = m or plan.pp * (
            plan.virtual_stages if plan.schedule == "interleaved"
            else 1
        )
        mb = batch // (plan.dp * n_mb)  # rows per microbatch
        shards = plan.pp * plan.tp_or_sp * plan.dp
        compute_s = cost.plan_step_compute_s(
            grad_bytes // 4, batch * 16, shards,
        )
        pred_s = cost.composed_plan_step_s(
            plan.pp, plan.tp_or_sp, plan.dp, grad_bytes, mb=mb,
            seq_len=16, dim=cfg.dim, vocab=cfg.vocab_size,
            n_layers=cfg.num_layers, ici=size, dcn=1,
            fsdp=plan.fsdp, schedule=plan.schedule,
            virtual_stages=plan.virtual_stages,
            num_microbatches=m or 0, compute_s=compute_s,
        )
        # The ledger twin carries the M suffix when the row pins one
        # (lint Combo names append /M<n> for explicit microbatches).
        combo_name = f"plan/S{size}/{spec}" + (f"/M{m}" if m else "")
        return _with_predicted(
            {
                "plan": spec,
                "schedule": plan.schedule,
                "axes": {"pp": plan.pp, "sp": plan.tp_or_sp,
                         "dp": plan.dp, "fsdp": plan.fsdp,
                         "virtual": plan.virtual_stages},
                "microbatches": n_mb,
                "step_ms": round(step_ms, 3),
                "model_predicted_ms": round(pred_s * 1e3, 4),
            },
            combo_name, measured_key="step_ms",
        )

    specs = [
        (f"dp{size}", None), (f"pp2xdp{size // 2}", None),
        (f"sp2xdp{size // 2}", None),
        (f"pp2xsp2xdp{size // 4}", None),
        # The schedule column (ISSUE 20): gpipe vs 1f1b vs int2 twins
        # of ONE factorization at fixed pp2 x M=4 — same mesh, same
        # collectives, different tick program; the ledger twins are
        # the /M4 combos the lint matrix pins.
        (f"pp2xdp{size // 2}", 4), (f"pp2-1f1bxdp{size // 2}", 4),
        (f"pp2-int2xdp{size // 2}", 4),
    ]
    rows = []
    for spec, m in specs:
        rows.append(_time_spec(spec, m))
        # Per-leg partial line (same convention as the other sweeps):
        # a kill mid-sweep keeps the finished factorizations.
        print(json.dumps({"leg": rows[-1], "partial": True}), flush=True)
    out = {
        "plan_microbench": rows,
        "run_meta": _run_meta(platform=jax.devices()[0].platform),
    }
    if knobs is not None:
        default = rows[0]  # the pure-data leg
        tuned = _time_spec(knobs["plan"])
        out["tuned"] = _tuned_row(
            size, knobs, combo, tuned["step_ms"],
            default["step_ms"], default["plan"],
        )
        print(json.dumps({"leg": out["tuned"], "partial": True}),
              flush=True)
    if jax.devices()[0].platform == "cpu":
        out["note"] = (
            "virtual CPU devices share one host core: the composed "
            "factorizations serialize their stage/seq collectives onto "
            "it, so step_ms ranks plans only on a real slice; "
            "model_predicted_ms is the alpha-beta TPU-fabric prediction "
            "the tuner ranks with"
        )
    print(json.dumps(out, indent=2))


def run_cm(max_devices: int, platform: str = "cpu",
           plan_path=None) -> None:
    """Naive-vs-overlapped collective-matmul microbench — the pjit
    microbenchmark TODO from SNIPPETS [2], pointed at the latency-hiding
    rings (`ops/collective_matmul.py`).

    For each 'model' ring size S the device count hosts, times the
    column->row projection pair (the per-transformer-block ag_matmul +
    matmul_rs sites) in BOTH lowerings: monolithic (one all-gather /
    one psum-scatter, overlap left to the scheduler) and chunked (S-1
    ppermutes, each hop overlapping the chunk dot), forward and
    forward+grad. Emits one partial JSON line per completed leg (axis
    size) — a kill mid-sweep keeps the finished legs — then the table.
    Meaningful on a real slice; on virtual CPU devices the ring serializes
    onto one core (the note in the JSON says so)."""
    if max_devices < 2:
        raise ValueError(f"--max-devices must be >= 2, got {max_devices}")
    if platform == "cpu":
        from distributed_model_parallel_tpu.runtime.platform import force_cpu

        force_cpu(max_devices)

    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_model_parallel_tpu.ops.collective_matmul import (
        ag_matmul,
        matmul_rs,
        naive_ag_matmul,
        naive_matmul_rs,
    )
    from distributed_model_parallel_tpu.runtime.compat import shard_map

    devices = jax.devices("cpu") if platform == "cpu" else jax.devices()
    sizes = []
    n = 2
    while n <= min(max_devices, len(devices)):
        sizes.append(n)
        n *= 2

    # Per-block projection pair at a transformer-ish aspect ratio; T
    # scales with S (fixed per-device chunk) like real seq sharding.
    batch, dmodel, dff = 4, 256, 1024
    rng = np.random.RandomState(0)
    w1 = jnp.asarray(0.02 * rng.randn(dmodel, dff), jnp.float32)
    w2 = jnp.asarray(0.02 * rng.randn(dff, dmodel), jnp.float32)

    def pair(col_fn, row_fn):
        def f(x, w1, w2):
            h = jax.nn.gelu(col_fn(x, w1, "model"), approximate=False)
            return row_fn(h, w2, "model")
        return f

    def time_fn(fn, args, iters=20):
        out = fn(*args)  # compile + warmup
        _ = jax.device_get(out.ravel()[0])
        t0 = time.perf_counter()
        for _i in range(iters):
            out = fn(*args)
        _ = jax.device_get(out.ravel()[0])  # real completion barrier
        return (time.perf_counter() - t0) / iters * 1e3

    plan_knobs, plan_combo = _bench_plan(
        plan_path, ("tp", "sp_lm"), "collective-matmul"
    )
    rows = []
    for size in sizes:
        mesh = Mesh(np.array(devices[:size]), ("model",))
        _note_mesh(mesh)
        x = jnp.asarray(
            0.1 * rng.randn(batch, 32 * size, dmodel), jnp.float32
        )
        specs = dict(
            mesh=mesh,
            in_specs=(P(None, "model", None), P(None, "model"),
                      P("model", None)),
            check_vma=False,
        )
        ring = jax.jit(shard_map(
            pair(ag_matmul, matmul_rs),
            out_specs=P(None, "model", None), **specs,
        ))
        mono = jax.jit(shard_map(
            pair(naive_ag_matmul, naive_matmul_rs),
            out_specs=P(None, "model", None), **specs,
        ))

        def gradded(f):
            def g(x, w1, w2):
                def loss(x, w1, w2):
                    y = f(x, w1, w2)
                    return jnp.sum(y * y)
                return jax.grad(loss, argnums=(0, 1, 2))(x, w1, w2)[0]
            return jax.jit(g)

        row = {
            "axis_size": size,
            "fwd_naive_ms": round(time_fn(mono, (x, w1, w2)), 3),
            "fwd_overlapped_ms": round(time_fn(ring, (x, w1, w2)), 3),
            "step_naive_ms": round(
                time_fn(gradded(mono), (x, w1, w2)), 3
            ),
            "step_overlapped_ms": round(
                time_fn(gradded(ring), (x, w1, w2)), 3
            ),
        }
        row["fwd_speedup"] = round(
            row["fwd_naive_ms"] / max(row["fwd_overlapped_ms"], 1e-9), 3
        )
        row["step_speedup"] = round(
            row["step_naive_ms"] / max(row["step_overlapped_ms"], 1e-9), 3
        )
        # Ledger column: the ag+rs op-level kernel pair this row times.
        ag = _ledger_predicted_ms(f"cm_ag/S{size}")
        rs = _ledger_predicted_ms(f"cm_rs/S{size}")
        if ag is not None and rs is not None:
            row["predicted_ms"] = round(ag + rs, 6)
            row["predicted_combo"] = f"cm_ag+cm_rs/S{size}"
            if row["predicted_ms"] > 0:
                row["delta_pct"] = round(
                    (row["fwd_overlapped_ms"] - row["predicted_ms"])
                    / row["predicted_ms"] * 100.0, 1
                )
        rows.append(row)
        log(f"S={size}: fwd {row['fwd_naive_ms']}ms naive vs "
            f"{row['fwd_overlapped_ms']}ms overlapped")
        # Per-leg partial line (same convention as the scaling sweep):
        # a kill mid-sweep keeps the finished axis sizes.
        print(json.dumps({"leg": row, "partial": True}), flush=True)
        if plan_knobs is not None:
            tuned_fn = gradded(
                ring if plan_knobs.get("collective_matmul") else mono
            )
            trow = _tuned_row(
                size, plan_knobs, plan_combo,
                time_fn(tuned_fn, (x, w1, w2)),
                row["step_naive_ms"], "step_naive_ms",
            )
            rows.append(trow)
            log(f"S={size} tuned: {trow['tuned_ms']}ms "
                f"({trow['tuned_vs_default_pct']:+.1f}% vs naive)")
            print(json.dumps({"leg": trow, "partial": True}),
                  flush=True)

    out = {
        "collective_matmul_microbench": rows,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "shapes": {"batch": batch, "seq_per_shard": 32,
                   "d_model": dmodel, "d_ff": dff},
        "run_meta": _run_meta(platform=jax.devices()[0].platform),
    }
    if jax.devices()[0].platform == "cpu":
        out["note"] = (
            "virtual CPU devices serialize the ring onto one core, so "
            "overlap cannot win here; the harness is meaningful on a "
            "real slice, where each hop's transfer runs beside the "
            "chunk dot"
        )
    print(json.dumps(out, indent=2))


def run_reducer(max_devices: int, platform: str = "cpu",
                plan_path=None) -> None:
    """Naive-vs-bucketed-vs-hierarchical gradient-reduction microbench
    (`ops/grad_reduction.py`) — the reducer counterpart of the
    collective-matmul table.

    For each data-parallel size S, times the mean-reduction of a
    ResNet-spread gradient pytree in three lowerings:
      * naive        — per-leaf `lax.pmean` over the flat data axis
                       (the unfused many-small-all-reduces shape this
                       backend lowers ResNet-50's DDP step to,
                       experiments/scaling64.py step 2);
      * bucketed     — dtype-grouped ~bucket_mb flat buckets, each a
                       chunked ppermute ring (reduce-scatter +
                       all-gather), single fabric;
      * hierarchical — the same buckets over a 2×(S/2) dcn×ici mesh:
                       ring reduce-scatter over 'ici', one cross-slice
                       all-reduce on the 1/S shard over 'dcn', ring
                       all-gather back.

    Plus the OVERLAPPED pair, which needs a backward to overlap with
    (a small staged MLP, `models/staging.staged_model`):
      * bwd_bucketed — jax.grad of the full model, THEN the bucketed
                       reduction (every ring serialized behind the
                       last backward dot);
      * overlapped   — the stagewise backward
                       (`staging.stagewise_value_and_grad`) firing each
                       segment's buckets eagerly, late layers first —
                       same math, rings data-dependent only on their
                       own segment.
    Emits one partial JSON line per completed size (a kill mid-sweep
    keeps the finished legs), then the table. Meaningful on a real
    slice; on virtual CPU devices the rings serialize onto one core
    (the note in the JSON says so)."""
    if max_devices < 2:
        raise ValueError(f"--max-devices must be >= 2, got {max_devices}")
    if platform == "cpu":
        from distributed_model_parallel_tpu.runtime.platform import force_cpu

        force_cpu(max_devices)

    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_model_parallel_tpu.ops.grad_reduction import (
        bucketed_pmean,
        plan_buckets,
    )
    from distributed_model_parallel_tpu.runtime.compat import shard_map

    devices = jax.devices("cpu") if platform == "cpu" else jax.devices()
    sizes = []
    n = 2
    while n <= min(max_devices, len(devices)):
        sizes.append(n)
        n *= 2

    # A ResNet-ish spread of gradient leaves (conv kernels, BN scales,
    # a head) totaling a few MB — enough for several 1 MB buckets
    # without drowning the CPU harness.
    rng = np.random.RandomState(0)
    shapes = (
        [(3, 3, 64, 64)] * 8 + [(1, 1, 256, 64)] * 4
        + [(512, 10)] + [(64,)] * 40 + [(256,)] * 20
    )
    grads = {
        f"g{i}": jnp.asarray(0.01 * rng.randn(*s), jnp.float32)
        for i, s in enumerate(shapes)
    }
    bucket_mb = 1.0
    n_bytes = sum(int(np.prod(s)) * 4 for s in shapes)
    n_buckets = len(
        plan_buckets(jax.tree_util.tree_leaves(grads), bucket_mb)
    )

    def fence(out):
        # Value-fetch barrier over EVERY leaf (see _sync): the naive
        # variant is 73 independent per-leaf reductions and the
        # bucketed ones several buckets — fetching one leaf would stop
        # the clock with most of the work still in flight.
        _ = jax.device_get(jnp.stack(
            [l.ravel()[0] for l in jax.tree_util.tree_leaves(out)]
        ))

    def time_fn(fn, iters=10):
        fence(fn(grads))  # compile + warmup
        t0 = time.perf_counter()
        for _i in range(iters):
            out = fn(grads)
        fence(out)
        return (time.perf_counter() - t0) / iters * 1e3

    def reducer(mesh, fn):
        spec = jax.tree_util.tree_map(lambda _: P(), grads)
        return jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
            check_vma=False,
        ))

    # ---- the overlapped pair's workload: a staged MLP whose backward
    # the eager buckets can hide behind (module docstring).
    from distributed_model_parallel_tpu.models import layers as L
    from distributed_model_parallel_tpu.models import staging
    from distributed_model_parallel_tpu.models.layers import Context

    mlp_blocks = [
        L.sequential(L.linear(256, 256), L.relu()) for _ in range(6)
    ]
    mlp = staging.staged_model(
        L.sequential(L.linear(64, 256), L.relu()),
        mlp_blocks,
        L.linear(256, 10),
    )
    mlp_params, mlp_state = mlp.init(jax.random.PRNGKey(0))
    mlp_cuts = staging.split_points(3, None, len(mlp_blocks))
    mlp_bucket_mb = 0.1
    ctx = Context(train=True)

    def mlp_loss(y):
        return 0.5 * jnp.sum(y * y)

    def bwd_then_bucketed(params, x):
        def loss(p):
            y, _ = mlp.apply(p, mlp_state, x, ctx)
            return mlp_loss(y)

        g = jax.grad(loss)(params)
        return bucketed_pmean(g, "data", bucket_mb=mlp_bucket_mb)

    def overlapped_bwd(params, x):
        fns = staging.stage_apply_fns(mlp.parts, mlp_cuts, ctx)
        _, _, stage_grads, _ = staging.stagewise_value_and_grad(
            fns,
            lambda y: (mlp_loss(y), ()),
            staging.partition_tree(params, mlp_cuts),
            staging.partition_tree(mlp_state, mlp_cuts),
            x,
            on_stage_grads=lambda k, g: bucketed_pmean(
                g, "data", bucket_mb=mlp_bucket_mb
            ),
        )
        return staging.unpartition_tree(stage_grads, mlp_cuts)

    def mlp_reducer(mesh, fn):
        pspec = jax.tree_util.tree_map(lambda _: P(), mlp_params)
        return jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(pspec, P("data")),
            out_specs=pspec, check_vma=False,
        ))

    def time_mlp(fn, x, iters=10):
        fence(fn(mlp_params, x))
        t0 = time.perf_counter()
        for _i in range(iters):
            out = fn(mlp_params, x)
        fence(out)
        return (time.perf_counter() - t0) / iters * 1e3

    plan_knobs, plan_combo = _bench_plan(
        plan_path, ("ddp", "fsdp", "sp_lm"), "reducer"
    )
    rows = []
    for size in sizes:
        flat_mesh = Mesh(np.array(devices[:size]), ("data",))
        naive = reducer(
            flat_mesh,
            lambda t: jax.tree_util.tree_map(
                lambda g: lax.pmean(g, "data"), t
            ),
        )
        bucketed = reducer(
            flat_mesh,
            partial(bucketed_pmean, ici_axis="data",
                    bucket_mb=bucket_mb),
        )
        hier_mesh = Mesh(
            np.array(devices[:size]).reshape(2, size // 2),
            ("dcn", "ici"),
        )
        hierarchical = reducer(
            hier_mesh,
            partial(bucketed_pmean, ici_axis="ici", dcn_axis="dcn",
                    bucket_mb=bucket_mb),
        )
        bwd_bucketed = mlp_reducer(flat_mesh, bwd_then_bucketed)
        overlapped = mlp_reducer(flat_mesh, overlapped_bwd)
        # Weak-scaling batch (8 rows/device) so the 'data' shard is
        # always whole and per-device backward work stays constant.
        mlp_x = jnp.asarray(rng.randn(8 * size, 64), jnp.float32)
        row = {
            "axis_size": size,
            "wire": "f32",
            "naive_ms": round(time_fn(naive), 3),
            "bucketed_ms": round(time_fn(bucketed), 3),
            "hierarchical_ms": round(time_fn(hierarchical), 3),
            "bwd_bucketed_ms": round(time_mlp(bwd_bucketed, mlp_x), 3),
            "overlapped_ms": round(time_mlp(overlapped, mlp_x), 3),
        }
        row["bucketed_speedup"] = round(
            row["naive_ms"] / max(row["bucketed_ms"], 1e-9), 3
        )
        row["hierarchical_speedup"] = round(
            row["naive_ms"] / max(row["hierarchical_ms"], 1e-9), 3
        )
        row["overlapped_speedup"] = round(
            row["bwd_bucketed_ms"] / max(row["overlapped_ms"], 1e-9), 3
        )
        # Ledger column keyed on the hierarchical leg's lint-matrix
        # twin (the 2 x S/2 dcn x ici bucketed reducer).
        _with_predicted(row, f"ddp/S{size}/dcn2/bucketed",
                        measured_key="hierarchical_ms")
        rows.append(row)
        log(f"S={size}: naive {row['naive_ms']}ms, bucketed "
            f"{row['bucketed_ms']}ms, hierarchical "
            f"{row['hierarchical_ms']}ms, bwd+bucketed "
            f"{row['bwd_bucketed_ms']}ms, overlapped "
            f"{row['overlapped_ms']}ms")
        # Per-leg partial line (same convention as the other sweeps).
        print(json.dumps({"leg": row, "partial": True}), flush=True)
        # Quantized-wire rows (ops/wire_codec.py): the SAME
        # hierarchical reduction with the cross-slice hop compressed —
        # the only leg the wire dtype touches, so the f32 columns are
        # not re-timed. On the CPU mesh the encode/decode ADDS work
        # (no real slow fabric to save); the column exists so a real
        # slice fills it in (the byte story is pinned by hlolint
        # dcn-compressed-payload either way).
        for wire in ("bf16", "int8"):
            hier_w = reducer(
                hier_mesh,
                partial(bucketed_pmean, ici_axis="ici",
                        dcn_axis="dcn", bucket_mb=bucket_mb,
                        dcn_compression=wire),
            )
            wrow = {
                "axis_size": size,
                "wire": wire,
                "hierarchical_ms": round(time_fn(hier_w), 3),
            }
            wrow["hierarchical_speedup"] = round(
                row["naive_ms"] / max(wrow["hierarchical_ms"], 1e-9), 3
            )
            _with_predicted(
                wrow,
                f"ddp/S{size}/dcn2/bucketed/wire-{wire}",
                f"ddp/S{size}/dcn2/bucketed/wire-{wire}/tinycnn",
                f"ddp/S{size}/dcn2/overlapped/wire-{wire}",
                measured_key="hierarchical_ms",
            )
            rows.append(wrow)
            log(f"S={size} wire={wire}: hierarchical "
                f"{wrow['hierarchical_ms']}ms")
            print(json.dumps({"leg": wrow, "partial": True}),
                  flush=True)
        if plan_knobs is not None:
            # The tuned configuration as an extra row on the same
            # hierarchical harness: the plan's bucket cap + wire on
            # the bucket-ring reduction ('overlapped' times its
            # bucket structure — this harness is the pure reduction;
            # uncompressed 'monolithic' is the fused tree pmean,
            # compressed monolithic the engines' single flat bucket).
            gr = plan_knobs.get("grad_reduction", "monolithic")
            twire = plan_knobs.get("dcn_compression", "none")
            if gr == "monolithic" and twire == "none":
                tuned = reducer(
                    hier_mesh,
                    lambda t: jax.tree_util.tree_map(
                        lambda g: lax.pmean(g, ("dcn", "ici")), t
                    ),
                )
            else:
                tuned = reducer(
                    hier_mesh,
                    partial(
                        bucketed_pmean, ici_axis="ici",
                        dcn_axis="dcn",
                        bucket_mb=(
                            plan_knobs.get("bucket_mb") or 1e9
                        ),
                        dcn_compression=twire,
                    ),
                )
            trow = _tuned_row(
                size, plan_knobs, plan_combo, time_fn(tuned),
                row["hierarchical_ms"], "hierarchical_ms",
            )
            rows.append(trow)
            log(f"S={size} tuned: {trow['tuned_ms']}ms "
                f"({trow['tuned_vs_default_pct']:+.1f}% vs "
                "hierarchical)")
            print(json.dumps({"leg": trow, "partial": True}),
                  flush=True)

    out = {
        "reducer_microbench": rows,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "grad_mb": round(n_bytes / 1e6, 2),
        "n_leaves": len(shapes),
        "bucket_mb": bucket_mb,
        "n_buckets": n_buckets,
        "hierarchy": "2 x S/2 (dcn x ici)",
        "overlapped_workload": (
            "staged MLP 64->256->10, 6 blocks, 3 backward segments, "
            f"bucket_mb={mlp_bucket_mb} (bwd_bucketed = grad then "
            "buckets; overlapped = stagewise eager firing)"
        ),
    }
    out["run_meta"] = _run_meta(platform=jax.devices()[0].platform)
    if jax.devices()[0].platform == "cpu":
        out["note"] = (
            "virtual CPU devices serialize the rings onto one core, so "
            "bucket overlap cannot win here; the harness is meaningful "
            "on a real slice, where per-bucket hops run beside the "
            "remaining backward and the dcn all-reduce crosses the "
            "slow fabric with 1/S of the bytes"
        )
    print(json.dumps(out, indent=2))


def run_moe(max_devices: int, platform: str = "cpu",
            plan_path=None) -> None:
    """Flat-vs-hierarchical-vs-overlapped MoE dispatch microbench
    (`ops/expert_dispatch.py`) — the expert-exchange counterpart of the
    reducer table.

    For each expert-parallel size S, times one MoE layer's
    exchange + expert FFN + return over a fixed (E, B/S, C, D) dispatch
    buffer in three lowerings:
      * flat         — ONE fused `lax.all_to_all` over the joint
                       fabric each way (the shape the GSPMD partitioner
                       picks; on a hybrid mesh the full payload crosses
                       'dcn' in (K-1)*I fragments);
      * hierarchical — the explicit two-level exchange on a 2 x (S/2)
                       dcn x ici mesh: intra-slice all-to-all over
                       'ici', ONE cross-slice exchange on the
                       1/ici-regrouped shard, all moe_ring ppermutes;
      * overlapped   — the same hops fused with the FFN: chunk k's
                       expert compute runs while chunk k+1's permute
                       (and chunk k's return) are in flight.

    Emits one partial JSON line per completed size (a kill mid-sweep
    keeps the finished legs), then the table. Meaningful on a real
    slice; on virtual CPU devices the rings serialize onto one core
    (the note in the JSON says so)."""
    if max_devices < 2:
        raise ValueError(f"--max-devices must be >= 2, got {max_devices}")
    if platform == "cpu":
        from distributed_model_parallel_tpu.runtime.platform import force_cpu

        force_cpu(max_devices)

    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from distributed_model_parallel_tpu.models.moe import expert_ffn
    from distributed_model_parallel_tpu.ops.expert_dispatch import (
        exchanged_expert_ffn,
        flat_expert_exchange,
        flat_expert_return,
    )
    from distributed_model_parallel_tpu.runtime.compat import shard_map

    devices = jax.devices("cpu") if platform == "cpu" else jax.devices()
    sizes = []
    n = 2
    while n <= min(max_devices, len(devices)):
        sizes.append(n)
        n *= 2

    # One MoE layer's worth of dispatch buffers: E experts, a per-shard
    # token load, capacity rows, model dim — a few MB, enough that the
    # exchange dominates on a real fabric without drowning the CPU
    # harness.
    E, BL, C, D, H = 16, 4, 8, 64, 128
    rng = np.random.RandomState(0)
    xin = jnp.asarray(rng.randn(E, BL * max(sizes), C, D), jnp.float32)
    w = {
        "w_in": jnp.asarray(0.02 * rng.randn(E, D, H), jnp.float32),
        "b_in": jnp.zeros((E, H), jnp.float32),
        "w_out": jnp.asarray(0.02 * rng.randn(E, H, D), jnp.float32),
        "b_out": jnp.zeros((E, D), jnp.float32),
    }
    payload_mb = xin.size * 4 / 1e6

    def fence(out):
        _ = jax.device_get(out.ravel()[0])

    def time_fn(fn, iters=10):
        fence(fn(xin, w))  # compile + warmup
        t0 = time.perf_counter()
        for _i in range(iters):
            out = fn(xin, w)
        fence(out)
        return (time.perf_counter() - t0) / iters * 1e3

    def build(mesh, names, body):
        dd = tuple(names)
        wspec = {
            k: P(dd, *([None] * (v.ndim - 1))) for k, v in w.items()
        }
        return jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(None, dd, None, None), wspec),
            out_specs=P(None, dd, None, None), check_vma=False,
        ))

    def flat_body(xl, wl, *, dd):
        z = flat_expert_exchange(xl, dd)
        y = expert_ffn(wl, z)
        return flat_expert_return(y, dd)

    plan_knobs, plan_combo = _bench_plan(plan_path, ("ep",), "MoE")
    rows = []
    for size in sizes:
        flat_mesh = Mesh(np.array(devices[:size]), ("data",))
        flat = build(
            flat_mesh, ("data",), partial(flat_body, dd=("data",))
        )
        hier_mesh = Mesh(
            np.array(devices[:size]).reshape(2, size // 2),
            ("dcn", "ici"),
        )
        _note_mesh(hier_mesh)

        def hier_body(xl, wl, overlap, wire="none"):
            return exchanged_expert_ffn(
                xl, partial(expert_ffn, wl), "ici", "dcn", overlap,
                wire,
            )

        hierarchical = build(
            hier_mesh, ("dcn", "ici"),
            partial(hier_body, overlap=False),
        )
        overlapped = build(
            hier_mesh, ("dcn", "ici"),
            partial(hier_body, overlap=True),
        )
        row = {
            "axis_size": size,
            "wire": "f32",
            "flat_ms": round(time_fn(flat), 3),
            "hierarchical_ms": round(time_fn(hierarchical), 3),
            "overlapped_ms": round(time_fn(overlapped), 3),
        }
        row["hierarchical_speedup"] = round(
            row["flat_ms"] / max(row["hierarchical_ms"], 1e-9), 3
        )
        row["overlapped_speedup"] = round(
            row["flat_ms"] / max(row["overlapped_ms"], 1e-9), 3
        )
        # Ledger column: the hybrid hierarchical-dispatch twin (the
        # matrix ships some sizes only in the overlapped variant).
        _with_predicted(
            row,
            f"ep/S{size}/dcn2/hierarchical",
            f"ep/S{size}/dcn2/hierarchical/ov",
            measured_key="hierarchical_ms",
        )
        rows.append(row)
        log(f"S={size}: flat {row['flat_ms']}ms, hierarchical "
            f"{row['hierarchical_ms']}ms, overlapped "
            f"{row['overlapped_ms']}ms")
        # Per-leg partial line (same convention as the other sweeps).
        print(json.dumps({"leg": row, "partial": True}), flush=True)
        # Quantized-wire rows: the two-level exchange with its 'dcn'
        # messages compressed (`ops/wire_codec.py`) — same hop
        # structure, 1/2 resp. 1/4 the cross-slice bytes (the reducer
        # table's caveat applies: on one CPU core the codec only adds
        # work; a real slice fills in the win).
        for wire in ("bf16", "int8"):
            hier_w = build(
                hier_mesh, ("dcn", "ici"),
                partial(hier_body, overlap=False, wire=wire),
            )
            over_w = build(
                hier_mesh, ("dcn", "ici"),
                partial(hier_body, overlap=True, wire=wire),
            )
            wrow = {
                "axis_size": size,
                "wire": wire,
                "hierarchical_ms": round(time_fn(hier_w), 3),
                "overlapped_ms": round(time_fn(over_w), 3),
            }
            wrow["hierarchical_speedup"] = round(
                row["flat_ms"] / max(wrow["hierarchical_ms"], 1e-9), 3
            )
            wrow["overlapped_speedup"] = round(
                row["flat_ms"] / max(wrow["overlapped_ms"], 1e-9), 3
            )
            _with_predicted(
                wrow,
                f"ep/S{size}/dcn2/hierarchical/wire-{wire}",
                f"ep/S{size}/dcn2/hierarchical/ov/wire-{wire}",
                measured_key="hierarchical_ms",
            )
            rows.append(wrow)
            log(f"S={size} wire={wire}: hierarchical "
                f"{wrow['hierarchical_ms']}ms, overlapped "
                f"{wrow['overlapped_ms']}ms")
            print(json.dumps({"leg": wrow, "partial": True}),
                  flush=True)
        if plan_knobs is not None:
            # The tuned dispatch as an extra row: the plan's
            # dispatch/overlap/wire knobs on the same exchange+FFN
            # harness, vs the flat (GSPMD-shaped) default leg.
            if plan_knobs.get("dispatch") == "gspmd":
                tuned = flat
            else:
                tuned = build(
                    hier_mesh, ("dcn", "ici"),
                    partial(
                        hier_body,
                        overlap=bool(plan_knobs.get("overlap")),
                        wire=plan_knobs.get(
                            "dcn_compression", "none"
                        ),
                    ),
                )
            trow = _tuned_row(
                size, plan_knobs, plan_combo, time_fn(tuned),
                row["flat_ms"], "flat_ms",
            )
            rows.append(trow)
            log(f"S={size} tuned: {trow['tuned_ms']}ms "
                f"({trow['tuned_vs_default_pct']:+.1f}% vs flat)")
            print(json.dumps({"leg": trow, "partial": True}),
                  flush=True)

    out = {
        "moe_microbench": rows,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "experts": E,
        "dispatch_payload_mb": round(payload_mb, 2),
        "hierarchy": "2 x S/2 (dcn x ici)",
        "workload": (
            f"one MoE layer's exchange+FFN+return over an "
            f"(E={E}, B, C={C}, D={D}) dispatch buffer, FFN hidden "
            f"{H}; flat = fused lax.all_to_all both ways, "
            "hierarchical/overlapped = the moe_ring two-level path"
        ),
    }
    out["run_meta"] = _run_meta(platform=jax.devices()[0].platform)
    if jax.devices()[0].platform == "cpu":
        out["note"] = (
            "virtual CPU devices serialize the rings onto one core, so "
            "chunk overlap cannot win here; the harness is meaningful "
            "on a real slice, where the cross-slice hops carry the "
            "1/ici-regrouped shard in K-1 contiguous messages and the "
            "per-chunk FFN hides them"
        )
    print(json.dumps(out, indent=2))


def run_serving(max_devices: int, platform: str = "cpu") -> None:
    """Serving microbench (`serving/engine.py`) — tokens/sec and
    p50/p99 per-token latency, prefill vs decode legs, per cache
    layout.

    For each layout the device count hosts (replicated; tp at S with
    the declarative lowering AND the opted-in decode rings; sp at S),
    times the two serving legs separately on a small GPT:

      * prefill — K single-request prompt ingests (the padded-prompt
        compile), per-call p50/p99 and prompt-tokens/sec;
      * decode  — N full-batch mixed-position token steps with every
        slot active, per-step p50/p99 and generated-tokens/sec.

    Emits one partial JSON line per completed (layout, S) row — a
    kill mid-sweep keeps the finished rows — then the table.
    Meaningful on a real slice; on virtual CPU devices the rings
    serialize onto one core (the note in the JSON says so).

    Three end-to-end legs follow the microbench: chunked-prefill
    admission vs monolithic, the prefix cache on/off, and speculative
    decoding at k in {2, 4} vs plain decode (ISSUE 18 — accept rate,
    tokens/s, and the lossless greedy pin, measured through eng.run
    on a weight-stream-bound model with an exact-prefix draft)."""
    if max_devices < 1:
        raise ValueError(f"--max-devices must be >= 1, got {max_devices}")
    if platform == "cpu":
        from distributed_model_parallel_tpu.runtime.platform import force_cpu

        force_cpu(max(max_devices, 1))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.observability.metrics import (
        exact_quantile,
    )
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec,
        make_mesh,
    )
    from distributed_model_parallel_tpu.serving.engine import ServingEngine

    from distributed_model_parallel_tpu.serving.scheduler import Request

    devices = jax.devices("cpu") if platform == "cpu" else jax.devices()
    num_slots, p_len, max_len, new_steps, n_prefills = 8, 16, 64, 32, 8
    page_size = 8
    cfg = GPTConfig(
        vocab_size=128, dim=64, num_layers=2, num_heads=4, ffn_dim=128,
        max_position=max_len, dropout_rate=0.0,
    )
    # (layout, axis size, collective_matmul, paged, compute_dtype) —
    # every contiguous leg has a paged twin so the table answers
    # paged-vs-contiguous per leg (prefill and decode separately), and
    # the quantized decode legs (ISSUE 16) ride the same harness with
    # f32 twins first so greedy-token stability is checked in-row.
    legs = [("replicated", 1, False, False, "f32"),
            ("replicated", 1, False, True, "f32")]
    for s in (2, 4):
        if s <= min(max_devices, len(devices)):
            legs += [("tp", s, False, False, "f32"),
                     ("tp", s, True, False, "f32"),
                     ("tp", s, True, True, "f32"),
                     ("sp", s, False, False, "f32"),
                     ("sp", s, False, True, "f32")]
    # Quantized decode floor: bf16/int8 at replicated plus the tp
    # rings (the lint matrix's q- combos price these shapes; off-TPU
    # the int8 GEMM takes the dtype-pinned XLA fallback, so the tok/s
    # column is about dispatch overhead until a real slice runs it —
    # predicted_ms carries the MXU-rate claim either way).
    legs += [("replicated", 1, False, False, "bf16"),
             ("replicated", 1, False, False, "int8")]
    for s in (2, 4):
        if s <= min(max_devices, len(devices)):
            legs += [("tp", s, True, False, "int8")]
    if 2 <= min(max_devices, len(devices)):
        legs += [("tp", 2, False, False, "int8"),
                 ("tp", 2, True, False, "bf16")]
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, cfg.vocab_size, size=p_len).astype(np.int32)

    rows = []
    greedy_ref = {}  # (layout, size, cm, paged) -> f32 argmax tokens
    for layout, size, cm, paged, cdt in legs:
        mesh = None
        if layout != "replicated":
            spec = MeshSpec(
                data=1,
                model=size if layout == "tp" else 1,
                seq=size if layout == "sp" else 1,
            )
            mesh = make_mesh(spec, devices=devices[:size])
            _note_mesh(mesh)
        eng = ServingEngine(
            cfg, mesh, layout=layout, num_slots=num_slots,
            max_len=max_len, prefill_len=p_len, collective_matmul=cm,
            page_size=page_size if paged else None,
            compute_dtype=cdt,
        )
        params = eng.init_params(jax.random.PRNGKey(0))
        ids, length = eng.pad_prompt(prompt)
        tokens = jnp.zeros((num_slots,), jnp.int32)
        active = jnp.ones((num_slots,), jnp.bool_)
        host = eng.new_host() if paged else None

        def do_prefill(cache, slot):
            if paged:
                host.ensure_pages(slot, p_len)
                return eng.prefill(
                    params, cache, host.device_row(slot), ids,
                    length,
                )
            return eng.prefill(
                params, cache, ids, length, jnp.int32(slot)
            )

        # Paged decode-leg bookkeeping is prepared OUTSIDE the timed
        # window (pages pre-allocated for every step, block table +
        # per-step positions uploaded once — `prep_decode`, called
        # AFTER the admission accounting snapshot below so the
        # at-prefill number stays honest): the timed region must be
        # the compiled step for BOTH cache layouts, or the
        # paged-vs-contiguous and delta_pct columns would charge host
        # Python to the paged device step.
        decode_args = {}

        def prep_decode():
            if not paged:
                return
            for slot in range(num_slots):
                # warmup + timed steps: one new position per call.
                host.ensure_pages(slot, p_len + new_steps + 2)
            decode_args["bt"] = host.device_table()
            decode_args["positions"] = [
                jnp.asarray(
                    np.full((num_slots,), p_len + i, np.int32)
                )
                for i in range(new_steps + 2)
            ]

        def do_decode(cache, step):
            if paged:
                return eng.decode_step(
                    params, cache, decode_args["bt"],
                    decode_args["positions"][step], tokens, active,
                )
            return eng.decode_step(params, cache, tokens, active)

        # --- prefill leg: fill every slot once (slot 0 is the warmup
        # compile), then re-ingest for the timed calls.
        cache = eng.init_cache()
        cache, nl = do_prefill(cache, 0)
        jax.block_until_ready(nl)
        for slot in range(1, num_slots):
            cache, nl = do_prefill(cache, slot)
        jax.block_until_ready(nl)
        prefill_ms = []
        for i in range(n_prefills):
            t0 = time.perf_counter()
            cache, nl = do_prefill(cache, i % num_slots)
            jax.block_until_ready(nl)
            prefill_ms.append((time.perf_counter() - t0) * 1e3)
        # Admission-time accounting snapshot: every slot holds a
        # p_len-token prompt, so paged allocation pins
        # ceil(p_len/page) pages per slot vs the contiguous layout's
        # max_len stripe (the decode leg below then grows it a token
        # per step — both numbers land in the row).
        prefill_kv_bytes = host.pool.kv_cache_bytes if paged else None

        # --- decode leg: every slot active at the prompt position.
        prep_decode()
        cache, logits = do_decode(cache, 0)
        jax.block_until_ready(logits)  # compile + warmup
        decode_ms = []
        greedy = []
        for i in range(new_steps):
            t0 = time.perf_counter()
            cache, logits = do_decode(cache, i + 1)
            jax.block_until_ready(logits)
            decode_ms.append((time.perf_counter() - t0) * 1e3)
            # Outside the timed window: the per-step argmax trajectory
            # for the quantized-vs-f32 greedy-stability column below.
            greedy.append(np.asarray(logits).argmax(axis=-1).tolist())

        # p50/p99 via the repo's ONE percentile rule
        # (observability/metrics.exact_quantile — the same math the
        # serving scheduler's latency report uses; pinned equal to the
        # retired numpy.percentile columns on canned latencies).
        pf, dc = np.asarray(prefill_ms), np.asarray(decode_ms)
        row = {
            "layout": layout + ("_cm" if cm else "")
            + ("_paged" if paged else "")
            + (f"_{cdt}" if cdt != "f32" else ""),
            "axis_size": size,
            "paged": paged,
            "compute_dtype": cdt,
            "prefill_p50_ms": round(exact_quantile(prefill_ms, 50), 3),
            "prefill_p99_ms": round(exact_quantile(prefill_ms, 99), 3),
            "prefill_tokens_per_s": round(
                p_len * len(pf) / (pf.sum() / 1e3), 1
            ),
            "decode_p50_ms": round(exact_quantile(decode_ms, 50), 3),
            "decode_p99_ms": round(exact_quantile(decode_ms, 99), 3),
            "decode_tokens_per_s": round(
                num_slots * len(dc) / (dc.sum() / 1e3), 1
            ),
        }
        if paged:
            # The PagedAttention accounting claim, from the pool
            # bookkeeping: allocated pages track live tokens
            # (p_len + decoded steps per slot), never slots*max_len.
            contiguous = num_slots * eng._slot_stripe_bytes
            row["kv_cache_bytes"] = host.pool.kv_cache_bytes
            row["contiguous_kv_bytes"] = contiguous
            row["kv_bytes_saved_pct"] = round(
                100.0 * (1 - host.pool.kv_cache_bytes / contiguous), 1
            )
            row["kv_bytes_saved_at_prefill_pct"] = round(
                100.0 * (1 - prefill_kv_bytes / contiguous), 1
            )
        # Greedy-token stability: the quantized leg must pick the SAME
        # argmax tokens as its f32 twin across every decode step, or
        # the compression is not free at temperature 0 on this config.
        key = (layout, size, cm, paged)
        if cdt == "f32":
            greedy_ref[key] = greedy
        elif key in greedy_ref:
            row["greedy_matches_f32"] = greedy == greedy_ref[key]
        if layout == "tp":
            # The lint matrix's serving combos are the tp decode step
            # (declarative, opted-in rings, the paged twins, and the
            # q- quantized variants).
            nm = f"serve/S{size}" + ("/pg8" if paged else "") \
                + ("/cm" if cm else "") \
                + (f"/q-{cdt}" if cdt != "f32" else "")
            _with_predicted(row, nm, measured_key="decode_p50_ms")
        rows.append(row)
        log(f"{row['layout']} S={size}: prefill p50 "
            f"{row['prefill_p50_ms']}ms, decode p50 "
            f"{row['decode_p50_ms']}ms "
            f"({row['decode_tokens_per_s']} tok/s)")
        # Per-leg partial line (same convention as the other sweeps).
        print(json.dumps({"leg": row, "partial": True}), flush=True)

    # --- admission leg: chunked prefill vs monolithic under a mixed
    # long-prompt/short-decode trace (Orca's iteration-level claim as
    # numbers: p99 TTFT and useful-slots-per-iteration, both from the
    # scheduler's existing report path). The monolithic deficiency the
    # ISSUE names is PADDING: every admission — a 3-token short
    # included — pays a prefill_len-padded compile sized for the
    # longest prompt, so a queue of shorts drains prefill_len/prompt
    # times slower than it should; the chunked engine pays
    # ceil(prompt/chunk) small chunks instead, and decode interleaves
    # with each one. Sized compute-dominant (dim 256) so the padding
    # waste, not CPU dispatch overhead, is what's measured.
    adm_max_len = 160
    adm_cfg = GPTConfig(
        vocab_size=128, dim=256, num_layers=2, num_heads=4,
        ffn_dim=1024, max_position=adm_max_len, dropout_rate=0.0,
    )

    def admission_trace():
        r = np.random.RandomState(1)
        reqs = [Request(
            rid=0,
            prompt=r.randint(1, 128, size=120).astype(np.int32),
            max_new_tokens=16,
        )]
        reqs += [Request(
            rid=1 + i,
            prompt=r.randint(
                1, 128, size=int(r.randint(3, 13))
            ).astype(np.int32),
            max_new_tokens=4,
        ) for i in range(20)]
        return reqs

    admission = {}
    for mode, chunk in (("monolithic", None), ("chunked", 16)):
        eng = ServingEngine(
            adm_cfg, layout="replicated", num_slots=4,
            max_len=adm_max_len,
            prefill_len=128 if chunk is None else 16,
            page_size=16, prefill_chunk=chunk,
        )
        params = eng.init_params(jax.random.PRNGKey(0))
        eng.run(params, admission_trace())  # warmup compiles
        sched = eng.run(params, admission_trace())
        rep = sched.latency_report()
        admission[mode] = {
            "prefill_chunk": chunk,
            "ttft_p99_ms": rep["ttft_p99_ms"],
            "ttft_p50_ms": rep["prefill_p50_ms"],
            "mean_iter_occupancy": rep["mean_iter_occupancy"],
            "mean_batch_occupancy": rep["mean_batch_occupancy"],
            "tokens_per_s": rep["tokens_per_s"],
        }
    mono, chnk = admission["monolithic"], admission["chunked"]
    admission["ttft_p99_improvement_pct"] = round(
        100.0 * (1 - chnk["ttft_p99_ms"] / mono["ttft_p99_ms"]), 1
    ) if mono["ttft_p99_ms"] else None
    admission["iter_occupancy_improvement_pct"] = round(
        100.0 * (chnk["mean_iter_occupancy"]
                 / mono["mean_iter_occupancy"] - 1), 1
    ) if mono["mean_iter_occupancy"] else None
    log(f"admission: ttft p99 {mono['ttft_p99_ms']} -> "
        f"{chnk['ttft_p99_ms']} ms, iter occupancy "
        f"{mono['mean_iter_occupancy']} -> "
        f"{chnk['mean_iter_occupancy']}")
    print(json.dumps(
        {"leg": {"admission": admission}, "partial": True}
    ), flush=True)

    # --- prefix-cache leg: a repeated system prompt across requests —
    # reused pages skip their prefill entirely.
    sys_prompt = rng.randint(1, cfg.vocab_size, size=24).astype(
        np.int32
    )
    prefix_reqs = [
        Request(
            rid=i,
            prompt=np.concatenate([
                sys_prompt,
                rng.randint(1, cfg.vocab_size, size=4).astype(np.int32),
            ]),
            max_new_tokens=4,
        )
        for i in range(6)
    ]
    prefix = {}
    for mode, pc in (("off", False), ("on", True)):
        eng = ServingEngine(
            cfg, layout="replicated", num_slots=2, max_len=max_len,
            prefill_len=p_len, page_size=page_size, prefill_chunk=8,
            prefix_cache=pc,
        )
        params = eng.init_params(jax.random.PRNGKey(0))
        eng.run(params, list(prefix_reqs))  # warmup compiles
        sched = eng.run(params, list(prefix_reqs))
        rep = sched.latency_report()
        prefix[mode] = {
            "ttft_p99_ms": rep["ttft_p99_ms"],
            "tokens_per_s": rep["tokens_per_s"],
            "prefix_hit_pct": (
                rep.get("prefix_cache", {}).get("prefix_hit_pct", 0.0)
            ),
        }
    log(f"prefix cache: hit {prefix['on']['prefix_hit_pct']}% of "
        f"prompt tokens, ttft p99 {prefix['off']['ttft_p99_ms']} -> "
        f"{prefix['on']['ttft_p99_ms']} ms")
    print(json.dumps({"leg": {"prefix_cache": prefix},
                      "partial": True}), flush=True)

    # --- speculative leg (ISSUE 18): draft-propose / one-pass-verify /
    # lossless-accept vs plain decode, end-to-end through eng.run. The
    # model is sized into the WEIGHT-STREAM regime speculation targets
    # (dim 768 spills the per-step parameter read out of cache even on
    # CPU; the tiny dim-64 microbench model above is dispatch-bound,
    # where no draft can pay for itself), and the draft is an exact
    # PREFIX of the target: the target's trailing three blocks have
    # their residual writes (attn.out, ffn.out) zeroed — making each an
    # identity block — so the 1-layer draft holding block 0's params
    # produces bit-identical logits. That pins accept_rate at 1.0: the
    # leg measures the MACHINERY's ceiling (rounds, rollback, verify
    # amortization) with the model-pair quality factored out; the
    # accept-dependent expectation is the cost engine's
    # `speculative_expected_tokens` column, reconciled via predicted_ms
    # (the closed-form roofline at THIS leg's dims — the replicated leg
    # has no lint-matrix combo, those are tp-shaped).
    from distributed_model_parallel_tpu.observability import cost

    spec_cfg = GPTConfig(
        vocab_size=128, dim=768, num_layers=4, num_heads=4,
        ffn_dim=3072, max_position=64, dropout_rate=0.0,
    )
    spec_draft_cfg = GPTConfig(
        vocab_size=128, dim=768, num_layers=1, num_heads=4,
        ffn_dim=3072, max_position=64, dropout_rate=0.0,
    )
    spec_slots, spec_plen, spec_new = 8, 8, 48

    def spec_engine(c, k):
        return ServingEngine(
            c, layout="replicated", num_slots=spec_slots, max_len=64,
            prefill_len=spec_plen, page_size=page_size,
            prefill_chunk=spec_plen, speculative_k=k,
        )

    spec_eng = spec_engine(spec_cfg, 0)
    spec_params = spec_eng.init_params(jax.random.PRNGKey(0))
    for blk in ("1", "2", "3"):  # identity blocks: residual writes -> 0
        for branch in ("attn", "ffn"):
            w = spec_params["blocks"][blk][branch]["out"]
            w["w"] = jnp.zeros_like(w["w"])
            w["b"] = jnp.zeros_like(w["b"])
    spec_draft_eng = spec_engine(spec_draft_cfg, 0)
    spec_draft_params = spec_draft_eng.init_params(jax.random.PRNGKey(1))
    spec_draft_params["stem"] = spec_params["stem"]
    spec_draft_params["blocks"]["0"] = spec_params["blocks"]["0"]
    spec_draft_params["head"] = spec_params["head"]
    spec_prompts = [
        rng.randint(1, 128, size=spec_plen).astype(np.int32)
        for _ in range(spec_slots)
    ]

    def spec_reqs():
        return [Request(rid=i, prompt=spec_prompts[i],
                        max_new_tokens=spec_new)
                for i in range(spec_slots)]

    # Closed-form roofline at the leg's true dims (shards=1): decode
    # step, verify step, and the amortized per-accepted-token round
    # cost at the leg's PINNED accept rate and true draft ratio (1 of
    # 4 layers). Units: ms to emit one token per slot — the same unit
    # as the measured step-equivalent below.
    spec_decode_pred_s = cost.serve_decode_compute_s(
        spec_cfg.num_layers, spec_cfg.dim, spec_cfg.ffn_dim, spec_slots,
    )
    speculative = {}
    spec_plain_rep = None
    spec_plain_tokens = None
    for k in (0, 2, 4):
        eng_k = spec_eng if k == 0 else spec_engine(spec_cfg, k)
        kwargs = {} if k == 0 else {
            "draft": spec_draft_eng,
            "draft_params": spec_draft_params,
        }
        eng_k.run(spec_params, spec_reqs(), **kwargs)  # warmup compile
        sched = eng_k.run(spec_params, spec_reqs(), **kwargs)
        rep = sched.latency_report()
        row = {
            "speculative_k": k,
            "tokens_per_s": rep["tokens_per_s"],
            "decode_p50_ms": rep["decode_p50_ms"],
            "decode_p99_ms": rep["decode_p99_ms"],
            "generated_tokens": rep["generated_tokens"],
            # ms per one-token-per-slot step-equivalent — comparable
            # across k (a verify round emits several per slot).
            "step_equiv_ms": round(
                spec_slots * 1e3 / rep["tokens_per_s"], 3
            ) if rep["tokens_per_s"] else None,
        }
        if k == 0:
            spec_plain_rep = rep
            spec_plain_tokens = {
                f.rid: f.tokens for f in sched.finished
            }
            row["predicted_ms"] = round(spec_decode_pred_s * 1e3, 6)
        else:
            sp = rep["speculative"]
            row.update({
                "accept_rate": sp["accept_rate"],
                "mean_accept_len": sp["mean_accept_len"],
                "verify_rounds": sp["verify_rounds"],
                "spec_tokens": sp["spec_tokens"],
                "draft_layers": spec_draft_cfg.num_layers,
                "speedup_vs_plain_pct": round(
                    100.0 * (rep["tokens_per_s"]
                             / spec_plain_rep["tokens_per_s"] - 1), 1
                ),
                # The lossless pin, in-row: greedy speculative output
                # must be BIT-IDENTICAL to the plain engine's.
                "greedy_matches_plain": all(
                    f.tokens == spec_plain_tokens[f.rid]
                    for f in sched.finished
                ),
                "predicted_ms": round(cost.serve_speculative_token_s(
                    spec_decode_pred_s,
                    cost.serve_verify_compute_s(
                        spec_cfg.num_layers, spec_cfg.dim,
                        spec_cfg.ffn_dim, spec_slots, k,
                    ),
                    k, accept_rate=sp["accept_rate"],
                    draft_cost_ratio=(
                        spec_draft_cfg.num_layers / spec_cfg.num_layers
                    ),
                ) * 1e3, 6),
            })
        row["predicted_src"] = (
            "cost closed form @ leg dims (HBM roofline, shards=1)"
        )
        if row["step_equiv_ms"] and row["predicted_ms"]:
            row["delta_pct"] = round(
                (row["step_equiv_ms"] - row["predicted_ms"])
                / row["predicted_ms"] * 100.0, 1
            )
        speculative[f"k{k}" if k else "plain"] = row
        log(f"speculative k={k}: {row['tokens_per_s']} tok/s"
            + (f" ({row['speedup_vs_plain_pct']:+.1f}% vs plain, "
               f"accept {row['accept_rate']})" if k else ""))
        print(json.dumps({"leg": {"speculative": row},
                          "partial": True}), flush=True)

    out = {
        "serving_microbench": rows,
        "serving_admission": admission,
        "serving_prefix": prefix,
        "serving_speculative": speculative,
        "page_size": page_size,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "model": {
            "dim": cfg.dim, "layers": cfg.num_layers,
            "heads": cfg.num_heads, "vocab": cfg.vocab_size,
        },
        "num_slots": num_slots,
        "prefill_len": p_len,
        "max_len": max_len,
        "run_meta": _run_meta(platform=jax.devices()[0].platform),
    }
    if jax.devices()[0].platform == "cpu":
        out["note"] = (
            "virtual CPU devices serialize the decode rings onto one "
            "core, so the tp/sp layouts cannot win here; the harness "
            "is meaningful on a real slice, where each ring hop's "
            "transfer runs beside the chunk dot and the head-sharded "
            "cache halves per-chip attention reads"
        )
    print(json.dumps(out, indent=2))


def run_checkpoint(max_devices: int, platform: str = "cpu") -> None:
    """Checkpoint-save microbench (`checkpointing/`) — what the train
    loop actually pays per snapshot, in three lowerings over an FSDP
    (1/N-sharded) state:

      * legacy_sync   — the reference-shaped path: gather every leaf to
                        host (per-leaf process_allgather on a real
                        multi-host mesh), one .npz from host 0
                        (`training/checkpoint.save_checkpoint`);
      * sharded_sync  — each process writes only its addressable
                        chunks + the manifest, inline
                        (`checkpointing.save_sharded`);
      * sharded_async — same files from the background writer thread:
                        the step path pays only the device->host
                        snapshot (step_blocked_ms), the I/O overlaps
                        the next steps (save_wall_ms = until wait()).

    Columns per row: save_wall_ms, step_blocked_ms (how long the call
    holds the train loop), bytes_per_host (actual file bytes this
    process wrote). One partial JSON line per completed row (a kill
    mid-sweep keeps the finished legs), then the table. Single-process
    both formats write the same total bytes; on a real pod the sharded
    rows split them 1/N per host and skip the gather entirely."""
    if max_devices < 2:
        raise ValueError(f"--max-devices must be >= 2, got {max_devices}")
    if platform == "cpu":
        from distributed_model_parallel_tpu.runtime.platform import force_cpu

        force_cpu(max_devices)

    import glob
    import shutil
    import tempfile

    import jax
    import numpy as np

    from distributed_model_parallel_tpu.checkpointing import (
        AsyncCheckpointer,
        restore_checkpoint,
        save_sharded,
    )
    from distributed_model_parallel_tpu.models import layers as L
    from distributed_model_parallel_tpu.parallel.fsdp import FSDPEngine
    from distributed_model_parallel_tpu.runtime.mesh import (
        MeshSpec,
        make_mesh,
    )
    from distributed_model_parallel_tpu.training.checkpoint import (
        save_checkpoint,
    )
    from distributed_model_parallel_tpu.training.optim import SGD

    devices = jax.devices("cpu") if platform == "cpu" else jax.devices()
    size = min(max_devices, len(devices))
    if size % 2:
        size -= 1
    mesh = make_mesh(MeshSpec(data=size), devices=devices[:size])
    _note_mesh(mesh)
    # A few-MB MLP so the file I/O is measurable without drowning the
    # CPU harness (SGD momentum doubles the state bytes).
    model = L.sequential(
        L.linear(256, 1024), L.relu(),
        L.linear(1024, 1024), L.relu(),
        L.linear(1024, 10),
    )
    engine = FSDPEngine(model, SGD(), mesh, donate=False)
    state = engine.init_state(jax.random.PRNGKey(0))
    state_mb = sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(state)
    ) / 1e6
    workdir = tempfile.mkdtemp(prefix="ckpt_microbench_")

    def dir_bytes(d):
        return sum(
            os.path.getsize(f)
            for f in glob.glob(os.path.join(d, "*"))
            if os.path.isfile(f)
        )

    iters = 5
    rows = []
    try:
        for mode in ("legacy_sync", "sharded_sync", "sharded_async"):
            d = os.path.join(workdir, mode)
            blocked, wall = [], []
            writer = (
                AsyncCheckpointer() if mode == "sharded_async" else None
            )
            for i in range(iters):
                t0 = time.perf_counter()
                if mode == "legacy_sync":
                    save_checkpoint(
                        d, engine.to_canonical(state), acc=0.0, epoch=i
                    )
                    t1 = t2 = time.perf_counter()
                else:
                    save_sharded(
                        d, state, acc=0.0, epoch=i, writer=writer
                    )
                    t1 = time.perf_counter()
                    if writer is not None:
                        writer.wait()
                    t2 = time.perf_counter()
                blocked.append((t1 - t0) * 1e3)
                wall.append((t2 - t0) * 1e3)
            row = {
                "mode": mode,
                "axis_size": size,
                "save_wall_ms": round(float(np.median(wall)), 3),
                "step_blocked_ms": round(float(np.median(blocked)), 3),
                "bytes_per_host": dir_bytes(d),
            }
            rows.append(row)
            log(f"{mode}: wall {row['save_wall_ms']}ms, blocked "
                f"{row['step_blocked_ms']}ms, "
                f"{row['bytes_per_host'] / 1e6:.2f} MB/host")
            # Per-leg partial line (same convention as the other sweeps).
            print(json.dumps({"leg": row, "partial": True}), flush=True)
        # Sanity: the async files must restore what the state holds.
        template = jax.tree_util.tree_map(
            np.asarray, jax.device_get(state)
        )
        restored, _, _ = restore_checkpoint(
            os.path.join(workdir, "sharded_async"), template
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(template),
            jax.tree_util.tree_leaves(restored),
        ):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    out = {
        "checkpoint_microbench": rows,
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "axis_size": size,
        "state_mb": round(state_mb, 2),
        "iters_per_mode": iters,
        "run_meta": _run_meta(platform=jax.devices()[0].platform),
    }
    if jax.devices()[0].platform == "cpu":
        out["note"] = (
            "single-process virtual mesh: both formats write the same "
            "total bytes from one host and the legacy gather is a "
            "device_get, so the async step_blocked_ms column is the "
            "honest signal here; on a real pod the sharded rows write "
            "1/N per host and skip the per-leaf process_allgather"
        )
    print(json.dumps(out, indent=2))


# ------------------------------------------------------------- entry point


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--scaling", action="store_true",
        help="print a weak-scaling table instead of the single "
             "benchmark line",
    )
    parser.add_argument("--max-devices", type=int, default=8)
    parser.add_argument(
        "--scaling-model", default="tinycnn",
        choices=("tinycnn", "mobilenetv2", "resnet50"),
        help="--scaling workload: tinycnn for the CPU mesh; resnet50 "
             "(the BASELINE.json north-star) with --scaling-platform "
             "default on a real slice",
    )
    parser.add_argument(
        "--scaling-platform", default="cpu", choices=("cpu", "default"),
        help="devices for --scaling and the microbenches: 'cpu' = "
             "virtual CPU mesh (structure and counts, not device "
             "speed); 'default' = the chips JAX finds",
    )
    parser.add_argument(
        "--cm-microbench", action="store_true",
        help="print a naive-vs-overlapped collective-matmul table "
             "(latency-hiding chunked rings, ops/collective_matmul.py) "
             "instead of the single benchmark line; devices from "
             "--scaling-platform / --max-devices",
    )
    parser.add_argument(
        "--reducer-microbench", action="store_true",
        help="print a naive-vs-bucketed-vs-hierarchical gradient-"
             "reduction table (DDP-Reducer flat buckets over dcn×ici, "
             "ops/grad_reduction.py) instead of the single benchmark "
             "line; devices from --scaling-platform / --max-devices",
    )
    parser.add_argument(
        "--moe-microbench", action="store_true",
        help="print a flat-vs-hierarchical-vs-overlapped MoE expert-"
             "dispatch table (two-level dcn×ici moe_ring exchange, "
             "ops/expert_dispatch.py) instead of the single benchmark "
             "line; devices from --scaling-platform / --max-devices",
    )
    parser.add_argument(
        "--serving-microbench", action="store_true",
        help="print a per-layout serving table (tokens/sec + p50/p99 "
             "per-token latency, prefill vs decode legs, over the "
             "slot-paged KV cache — serving/engine.py) instead of the "
             "single benchmark line; devices from --scaling-platform / "
             "--max-devices",
    )
    parser.add_argument(
        "--checkpoint-microbench", action="store_true",
        help="print a legacy-sync vs sharded-sync vs sharded-async "
             "checkpoint-save table (save wall-ms, step-blocked-ms, "
             "bytes/host — checkpointing/) instead of the single "
             "benchmark line; devices from --scaling-platform / "
             "--max-devices",
    )
    parser.add_argument(
        "--plan-microbench", action="store_true",
        help="print a composed-ParallelPlan table (one tiny-GPT train "
             "step per mesh factorization — pure-data vs pp2/sp2 "
             "composed specs through build_plan_engine, "
             "parallel/plan.py — with the alpha-beta "
             "composed_plan_step_s prediction per row) instead of the "
             "single benchmark line; devices from --scaling-platform "
             "/ --max-devices",
    )
    parser.add_argument(
        "--plan", default=None, metavar="PLAN.json",
        help="time a tuner plan's chosen configuration "
             "(tuning/plan.py, --auto-tune search's artifact) as an "
             "extra row on the --reducer-microbench / --cm-microbench "
             "/ --moe-microbench / --plan-microbench tables, with a "
             "tuned_vs_default_pct column against the table's "
             "default-knob leg",
    )
    return parser


def main(argv=None) -> int:
    """Run one mode in this process; the exit code. A mode that raises
    propagates (non-zero exit, no metric line)."""
    parser = build_parser()
    args = parser.parse_args(argv)
    n_sweeps = sum(
        (args.scaling, args.cm_microbench, args.reducer_microbench,
         args.moe_microbench, args.serving_microbench,
         args.checkpoint_microbench, args.plan_microbench)
    )
    if n_sweeps > 1:
        parser.error(
            "--scaling / --cm-microbench / --reducer-microbench / "
            "--moe-microbench / --serving-microbench / "
            "--checkpoint-microbench / --plan-microbench are mutually "
            "exclusive (one sweep per invocation; running several "
            "would silently drop tables)"
        )
    if args.plan and not (
        args.reducer_microbench or args.cm_microbench
        or args.moe_microbench or args.plan_microbench
    ):
        parser.error(
            "--plan adds a tuned row to the reducer/cm/moe/plan "
            "microbenches; pass one of --reducer-microbench / "
            "--cm-microbench / --moe-microbench / --plan-microbench "
            "with it"
        )
    if args.plan and not os.path.isfile(args.plan):
        parser.error(f"--plan: no such file {args.plan!r}")

    from distributed_model_parallel_tpu.runtime.platform import (
        enable_compile_cache,
    )

    enable_compile_cache()
    devices, platform = args.max_devices, args.scaling_platform
    if args.scaling:
        run_scaling(devices, args.scaling_model, platform)
    elif args.cm_microbench:
        run_cm(devices, platform, args.plan)
    elif args.reducer_microbench:
        run_reducer(devices, platform, args.plan)
    elif args.moe_microbench:
        run_moe(devices, platform, args.plan)
    elif args.plan_microbench:
        run_plan_bench(devices, platform, args.plan)
    elif args.serving_microbench:
        run_serving(devices, platform)
    elif args.checkpoint_microbench:
        run_checkpoint(devices, platform)
    else:
        try:
            run_headline()
        except NoAcceleratorError as e:
            print(f"bench.py: {e}", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
