"""Epoch-driver trainer loops — the TPU-native `utils.py`/`data_parallel.py`
trainer surface.

Reproduces the reference's observable training behavior (SURVEY.md §5):
* per-batch loop with `batch_time` / `data_time` running averages —
  the two metrics the reference hand-accumulates (`utils.py:36-76`) and
  reports in its tables (`Readme.md:283-292`);
* progress print every `print_freq` batches (30 in the reference —
  `data_parallel.py:116-117`, `utils.py:69-70`);
* acc1/acc5 via the `accuracy(topk=(1,5))` contract (`utils.py:215-229`);
* per-epoch log line appended to a txt file (`data_parallel.py:167-171`,
  `model_parallel.py:119-125`) — plus structured JSONL, host-0 only;
* best-val-acc checkpointing and `--resume` (`data_parallel.py:80-87,
  143-155`), via `training/checkpoint.py`;
* cosine LR (T_max=90) with 10-epoch linear-warmup dampening stepped once
  per epoch (`data_parallel.py:90-96,163-164`).

Timing is fence-correct: JAX dispatch is async, so per-epoch averages are
computed from a fenced epoch wall clock, not from unfenced per-step deltas
(which would measure dispatch latency, not execution). The fence is a
VALUE FETCH of the epoch's summed metrics, which the loop needs on the
host anyway; on the v5e it and `block_until_ready` time the same work to
within 0.3% (chip_smoke.py's barrier leg, PERF.md).
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Iterable, Optional

import jax
import jax.numpy as jnp

from distributed_model_parallel_tpu.checkpointing import (
    AsyncCheckpointer,
    restore_checkpoint,
    save_sharded,
)
from distributed_model_parallel_tpu.observability.metrics import (
    get_metrics,
)
from distributed_model_parallel_tpu.observability.trace import get_tracer
from distributed_model_parallel_tpu.runtime.dist import is_primary
from distributed_model_parallel_tpu.training.checkpoint import (
    newest_checkpoint_name,
    save_checkpoint,
)
from distributed_model_parallel_tpu.training.multistep import (
    add_step_metrics,
    compile_multi_eval,
    compile_multi_step,
    group_batches,
)
from distributed_model_parallel_tpu.training.optim import (
    cosine_warmup_schedule,
)


@dataclasses.dataclass
class EpochStats:
    """What the reference logs per epoch (`model_parallel.py:119-125`)."""

    loss: float = 0.0
    acc1: float = 0.0
    acc5: float = 0.0
    batch_time: float = 0.0  # avg seconds per batch, data included
    data_time: float = 0.0   # avg seconds waiting on the input pipeline
    count: int = 0
    # What the engine's step metrics hold beside the four above (a model
    # family's counters, `models/lm_family.LMFamily.counters`), combined
    # over the epoch's steps as the engine's `metric_reductions` says.
    counters: dict = dataclasses.field(default_factory=dict)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class TrainerConfig:
    """Trainer hyperparameters, flag-for-flag with the reference parsers
    (`data_parallel.py:19-23`, `model_parallel.py:15-42`); hard-coded
    reference values (epochs=100, T_max=90, print-every-30) become
    defaults."""

    epochs: int = 100
    base_lr: float = 0.1
    t_max: int = 90
    warmup_period: int = 10
    print_freq: int = 30
    log_dir: str = "./log"
    log_file: Optional[str] = None      # txt epoch log (e.g. "512.txt")
    checkpoint_dir: str = "./checkpoint"
    save_best: bool = True
    resume: bool = False
    # Truncate each training epoch to N batches (0 = full epoch) — for
    # smoke runs and throughput benchmarking.
    steps_per_epoch: int = 0
    # Capture a jax.profiler trace of a few steady-state train steps
    # (compile and warmup excluded) into this directory; None disables.
    # The trace is the tool for attributing a bad MFU number (SURVEY.md §5
    # tracing row) — open with TensorBoard or xprof.
    profile_dir: Optional[str] = None
    # Also write a 'last' checkpoint at the END of every epoch (not just
    # on best val acc). This is what makes a run restartable after a
    # failure — the elastic driver loop (`training/elastic.py`) resumes
    # from it; `--resume` prefers it over the best-acc snapshot when it
    # is newer.
    save_last: bool = False
    # Checkpoint on-disk format: "legacy" = the reference-shaped single
    # .npz gathered to host 0 (`training/checkpoint.py`); "sharded" =
    # each process writes only its locally-addressable shards plus a
    # JSON manifest (`checkpointing/` — ZeRO-style parallel save, no
    # cross-process gather anywhere on the save path, and restore can
    # RESHARD onto a different mesh). Restore auto-detects either
    # format regardless of this setting.
    checkpoint_format: str = "legacy"
    # Move checkpoint file I/O off the step path (sharded format only):
    # the save snapshots device->host once, then a background thread
    # writes the files while training continues. Write errors are NEVER
    # silent — they surface at the next save or at fit() exit
    # (`checkpointing/writer.py`).
    async_save: bool = False
    # Extra JSON-able metadata stored in the checkpoint sidecar /
    # manifest (e.g. the lm CLI records its GPTConfig so `cli/serve.py
    # --checkpoint` can fail fast on a flag mismatch).
    checkpoint_extra: Optional[dict] = None
    # Fold this many optimizer steps into ONE compiled dispatch
    # (lax.scan over stacked batches — `training/multistep.py`). The
    # training trajectory matches per-step dispatch to numerical
    # tolerance (same math; XLA may fuse across step boundaries
    # differently — pinned at rtol 1e-5 in tests/test_trainer.py); what
    # changes is the host->device round-trip count.
    # Epoch tails shorter than the group fall back to per-step dispatch
    # (one extra compile the first time a tail occurs). 1 = off.
    steps_per_dispatch: int = 1


class Trainer:
    """Drives an engine (DP / DDP / pipeline — anything exposing
    `train_step`, `eval_step`, `shard_batch`, `init_state`) through the
    reference's epoch protocol."""

    def __init__(
        self,
        engine: Any,
        train_loader: Iterable,
        val_loader: Optional[Iterable],
        config: TrainerConfig,
        rng: Optional[jax.Array] = None,
    ):
        self.engine = engine
        # {name: "sum" | "max"} of the step metrics that are not the
        # four every engine has; {} for most engines.
        self._reductions = getattr(engine, "metric_reductions", None) or {}
        self.train_loader = train_loader
        self.val_loader = val_loader
        self.config = config
        if config.checkpoint_format not in ("legacy", "sharded"):
            raise ValueError(
                "checkpoint_format must be 'legacy' or 'sharded', got "
                f"{config.checkpoint_format!r}"
            )
        if config.async_save and config.checkpoint_format != "sharded":
            raise ValueError(
                "async_save moves the sharded writer off the step path; "
                "it requires checkpoint_format='sharded' (the legacy "
                "format gathers to host 0 synchronously by design)"
            )
        self._ckpt_writer = (
            AsyncCheckpointer() if config.async_save else None
        )
        self.lr_fn = cosine_warmup_schedule(
            config.base_lr, config.t_max, config.warmup_period
        )
        self.state = engine.init_state(
            rng if rng is not None else jax.random.PRNGKey(0)
        )
        self.best_acc = 0.0
        self.start_epoch = 0
        if config.resume:
            # Resume from whichever snapshot is NEWER by its recorded
            # epoch: the per-epoch 'last' (written under save_last) when
            # it is ahead of the best-acc 'ckpt', so an elastic restart
            # loses at most the failed epoch — but a stale 'last' from an
            # older run never rolls a newer 'ckpt' back. Only host 0's
            # files matter: restore_checkpoint broadcasts host-0's read.
            name = newest_checkpoint_name(config.checkpoint_dir)
            restored, self.best_acc, last_epoch = restore_checkpoint(
                config.checkpoint_dir, self._to_canonical(self.state),
                name=name,
            )
            self.state = self._from_canonical(restored)
            self.start_epoch = last_epoch + 1
            self._log_print(
                f"==> Resumed from checkpoint: epoch {last_epoch}, "
                f"best acc {self.best_acc:.3f}"
            )
            if self.start_epoch >= config.epochs:
                # Deliberate deviation from the reference, which always
                # trains `epochs` FURTHER epochs on resume
                # (`data_parallel.py:160`); here fit() runs
                # range(start_epoch, epochs), so resuming a finished run
                # is a no-op — say so instead of silently returning.
                self._log_print(
                    f"==> WARNING: checkpoint is at epoch {last_epoch} but "
                    f"--epochs is {config.epochs}; fit() will train 0 "
                    f"epochs. Raise --epochs to continue training."
                )
        self.history: list[dict] = []
        self._profiled = False
        self._multi = None       # lazily compiled k-step train dispatch
        self._multi_eval = None  # lazily compiled k-batch eval dispatch

    # ------------------------------------------------------------- loops

    def train_epoch(self, epoch: int) -> EpochStats:
        cfg = self.config
        # Host-phase spans (observability/trace.py; off by default —
        # one branch per site): fetch = host load + device placement,
        # step = the dispatch call (enqueue under async dispatch),
        # sync = the value-fetch fences where device time surfaces,
        # checkpoint_blocked = how long a save holds this loop
        # (_write_checkpoint). The metrics registry
        # (observability/metrics.py; same off-by-default discipline)
        # mirrors the phases as distributions: train_fetch_s /
        # train_step_s histograms, timestamps from the tracer's
        # injectable clock so tests stay deterministic.
        tracer = get_tracer()
        mx = get_metrics()
        lr = jnp.asarray(self.lr_fn(epoch), jnp.float32)
        if hasattr(self.train_loader, "set_epoch"):
            # Re-seed the per-epoch shuffle + augmentation RNG (the torch
            # DataLoader reshuffles per epoch; our Loader keys on epoch).
            self.train_loader.set_epoch(epoch)
        it = iter(self.train_loader)
        sums = None
        n_batches = 0
        data_time = 0.0
        # Profile steps 10-12 of the first profiled epoch (past compile and
        # cache warmup); short smoke epochs profile from the first step so
        # the capture is never silently empty.
        # Batches this epoch can actually yield: the loader length
        # bounded by the steps_per_epoch truncation (None = unknown).
        # One source of truth for the profiler window AND the dispatch
        # clamp below.
        n_avail = (
            len(self.train_loader)
            if hasattr(self.train_loader, "__len__") else None
        )
        if cfg.steps_per_epoch:
            n_avail = (
                min(n_avail, cfg.steps_per_epoch)
                if n_avail else cfg.steps_per_epoch
            )
        profiling = False
        k = max(1, cfg.steps_per_dispatch)
        if n_avail is not None and k > n_avail:
            # A group larger than the epoch would NEVER fill, silently
            # degrading every epoch to per-step dispatch (the gap this
            # feature exists to close) — clamp so at least one fused
            # dispatch runs per epoch.
            if not getattr(self, "_warned_k_clamp", False):
                self._log_print(
                    f"==> steps_per_dispatch {k} exceeds the "
                    f"{n_avail}-batch epoch; clamping to {n_avail}"
                )
                self._warned_k_clamp = True
            k = max(1, n_avail)
        profile_at = None
        if cfg.profile_dir and not self._profiled:
            profile_at = 10 if (n_avail is None or n_avail > 12) else 0
            if profile_at and k > 1:
                # Dispatches happen at group granularity: arm at the
                # first group START past the warmup threshold so the
                # capture excludes the fused program's trace+compile.
                # When no later group exists (the epoch fits in one),
                # fall back to profiling the first dispatch — capturing
                # compile beats an empty trace directory.
                ga = ((profile_at + k - 1) // k) * k
                profile_at = (
                    ga if (n_avail is None or ga < n_avail) else 0
                )
        def fetch_group(n_done: int):
            """Pull + device-place the next dispatch group (up to k host
            batches, bounded by the steps_per_epoch budget; [] when the
            epoch is exhausted). Host loading is what data_time measures;
            shard_batch transfers are enqueued asynchronously, so calling
            this right after a dispatch stages the NEXT group's arrays
            while the current device step is still in flight."""
            nonlocal data_time
            want = k
            if cfg.steps_per_epoch:
                want = min(k, cfg.steps_per_epoch - n_done)
                if want <= 0:
                    return []
            with tracer.span("fetch", want=want):
                t0 = time.perf_counter()
                tm0 = tracer.now() if mx.enabled else 0.0
                host_batches = group_batches(it, want)
                data_time += time.perf_counter() - t0
                if mx.enabled and host_batches:
                    # Metric clock = tracer clock (injectable), like
                    # train_step_s; data_time keeps the wall clock the
                    # reference's report fields are defined on.
                    mx.observe(
                        "train_fetch_s",
                        (tracer.now() - tm0) / len(host_batches),
                    )
                return [
                    self.engine.shard_batch(*b) for b in host_batches
                ]

        epoch_start = time.perf_counter()
        # Metrics state: the step-time boundary clock (tracer domain,
        # so tests inject it) and the one-deep progress-print snapshot
        # (n_batches, metrics) of the PREVIOUS dispatch group.
        t_boundary = tracer.now() if mx.enabled else None
        printable = None
        placed = fetch_group(0)
        while placed:
            if (
                profile_at is not None
                and not profiling
                and n_batches >= profile_at
            ):
                # Arm on the first dispatch whose START is past the
                # warmup threshold — a group that merely SPANS it would
                # capture the k-step program's trace+compile, the cost
                # the offset exists to exclude.
                jax.block_until_ready(self.state)  # trace excludes backlog
                jax.profiler.start_trace(cfg.profile_dir)
                profiling = True
            with tracer.span("step", n=len(placed)):
                if len(placed) == k and k > 1:
                    # One dispatch, k steps (trajectory matches the
                    # per-step path to numerical tolerance —
                    # tests/test_trainer.py).
                    if self._multi is None:
                        self._multi = compile_multi_step(self.engine, k)
                    self.state, metrics = self._multi(
                        self.state, tuple(placed), lr
                    )
                else:
                    metrics = None
                    for b in placed:
                        self.state, m_i = self.engine.train_step(
                            self.state, *b, lr
                        )
                        metrics = self._add(metrics, m_i)
            prev = n_batches
            n_group = len(placed)
            n_batches += n_group
            # One-deep device prefetch: the dispatch above returned at
            # enqueue time, so the next group's host load + placement
            # overlaps the in-flight compute — and, crucially, runs
            # BEFORE the progress print's device_get below fences on
            # that compute: anything sequenced after the fence is time
            # the device spends without its next batch staged.
            placed = fetch_group(n_batches)
            if profiling and n_batches >= profile_at + 3:
                jax.block_until_ready(self.state)
                jax.profiler.stop_trace()
                profiling = False
                self._profiled = True
                profile_at = None  # never re-arm within this epoch
            sums = self._add(sums, metrics)
            if mx.enabled:
                # Step-time sample at dispatch granularity (boundary
                # to boundary, prefetch included), CLOSED before the
                # progress-print fetch below so the histogram can
                # never measure its own readback stall.
                t_now = tracer.now()
                if t_boundary is not None:  # None: enabled mid-epoch
                    mx.observe(
                        "train_step_s", (t_now - t_boundary) / n_group
                    )
                mx.inc("train_batches_total", n_group)
                t_boundary = t_now
            if cfg.print_freq and (
                n_batches // cfg.print_freq > prev // cfg.print_freq
            ):
                # Fetch the PREVIOUS group's metrics (the one-deep
                # snapshot seam, same shape as the input prefetch): a
                # newer dispatch already runs behind them, so this
                # device_get returns without fencing the in-flight
                # compute — the progress print no longer injects a
                # readback stall into the loop it reports on
                # (regression-pinned with an injected slow clock in
                # tests/test_observability.py).
                # The first print of an epoch has no predecessor and
                # falls back to fencing the current group.
                snap_n, snap_metrics = (
                    printable if printable is not None
                    else (n_batches, metrics)
                )
                with tracer.span("sync"):
                    m = jax.device_get(snap_metrics)
                self._log_print(
                    f"Epoch: [{epoch}]"
                    f"[{snap_n}/{n_avail if n_avail is not None else '?'}]"
                    f"\tLoss {m['loss_sum'] / m['count']:.4e}"
                    f"\tAcc@1 {100.0 * m['correct1'] / m['count']:.3f}"
                    f"\tTime {(time.perf_counter() - epoch_start) / n_batches:.3f}"
                )
            printable = (n_batches, metrics)
        # Value-fetch barrier: fetching the summed metrics' bytes cannot
        # complete before every step that fed the sum has executed.
        if sums is not None:
            with tracer.span("sync", epoch=epoch):
                sums = jax.device_get(sums)
        if profiling:  # epoch ended inside the capture window
            jax.profiler.stop_trace()
            self._profiled = True
        wall = time.perf_counter() - epoch_start
        stats = self._finalize(sums, n_batches, wall, data_time)
        for name, value in stats.counters.items():
            # The model family's step counters (models/moe.COUNTERS),
            # as the epoch leaves them.
            mx.gauge(name, value)
        local = getattr(self.engine, "local_attention", None)
        if local is not None:
            mx.gauge("train_local_attention_flash", float(local == "flash"))
        return stats

    def validate(self, epoch: int) -> EpochStats:
        it = iter(self.val_loader)
        sums = None
        n_batches = 0
        data_time = 0.0
        k = max(1, self.config.steps_per_dispatch)
        if hasattr(self.val_loader, "__len__"):
            k = max(1, min(k, len(self.val_loader)))
        epoch_start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            host_batches = group_batches(it, k)
            data_time += time.perf_counter() - t0
            if not host_batches:
                break
            placed = [self.engine.shard_batch(*b) for b in host_batches]
            if len(placed) == k and k > 1:
                if self._multi_eval is None:
                    self._multi_eval = compile_multi_eval(self.engine, k)
                metrics = self._multi_eval(self.state, tuple(placed))
            else:
                metrics = None
                for b in placed:
                    m_i = self.engine.eval_step(self.state, *b)
                    metrics = self._add(metrics, m_i)
            sums = self._add(sums, metrics)
            n_batches += len(placed)
        if sums is not None:
            sums = jax.device_get(sums)  # value-fetch barrier, as above
        wall = time.perf_counter() - epoch_start
        return self._finalize(sums, n_batches, wall, data_time)

    def fit(self) -> dict:
        """The 100-epoch driver loop (`data_parallel.py:160-172`): train,
        validate, checkpoint on best acc, append the epoch log line."""
        try:
            return self._fit()
        except BaseException:
            # The failure path (exactly where the elastic supervisor
            # restarts from) must still DRAIN in-flight background
            # writes: the restart reads this checkpoint directory
            # immediately, and racing a half-committed save would hand
            # it yesterday's (or no) manifest. A write failure here is
            # printed, not raised — masking the training exception
            # would hide the error the supervisor's retry_on keys on.
            if self._ckpt_writer is not None:
                try:
                    self._ckpt_writer.wait()
                except Exception as we:  # noqa: BLE001 — reported below
                    self._log_print(
                        "==> WARNING: background checkpoint write "
                        f"failed during abort: {we!r}"
                    )
            raise

    def _fit(self) -> dict:
        cfg = self.config
        for epoch in range(self.start_epoch, cfg.epochs):
            train_stats = self.train_epoch(epoch)
            val_stats = (
                self.validate(epoch)
                if self.val_loader is not None
                else EpochStats()
            )
            is_best = (
                cfg.save_best
                and self.val_loader is not None
                and val_stats.acc1 > self.best_acc
            )
            if is_best or cfg.save_last:
                payload = self._checkpoint_payload()  # once per epoch
            if is_best:
                self.best_acc = val_stats.acc1
                self._log_print("Saving..")
                self._write_checkpoint(payload, "ckpt", epoch)
            if cfg.save_last:
                # acc records the best-so-far (restored into best_acc on
                # resume) — storing this epoch's val acc here would let a
                # restart reset best_acc downward and a worse model later
                # overwrite the best snapshot.
                self._write_checkpoint(payload, "last", epoch)
            self._append_epoch_log(epoch, train_stats, val_stats)
        if self._ckpt_writer is not None:
            # fit() exit is the LAST surfacing point for async write
            # errors (checkpointing/writer.py: never silent) and the
            # join guaranteeing the final snapshot is durable on return.
            self._ckpt_writer.wait()
        return {
            "best_acc": self.best_acc,
            "epochs": cfg.epochs,
            "history": self.history,
        }

    # ----------------------------------------------------------- helpers

    def _checkpoint_payload(self):
        """The tree handed to the checkpoint writer: the host-gathered
        canonical form for the legacy format; for the sharded format,
        the engine's DEVICE-SHARDED state via the `to_canonical_sharded`
        seam (canonical tree structure, values still 1/N per process —
        each process then persists only its addressable chunks and no
        cross-process gather runs anywhere on the save path)."""
        if self.config.checkpoint_format == "legacy":
            return self._to_canonical(self.state)
        fn = getattr(self.engine, "to_canonical_sharded", None)
        if fn is not None:
            return fn(self.state)
        if getattr(self.engine, "to_canonical", None) is not None:
            raise ValueError(
                f"{type(self.engine).__name__} defines a RESTRUCTURING "
                "canonical form (to_canonical) without a "
                "to_canonical_sharded seam, so its runtime layout "
                "cannot be written shard-for-shard; use "
                "checkpoint_format='legacy' with this engine"
            )
        return self.state  # state IS canonical (DP/DDP/SP engines)

    def _write_checkpoint(self, payload, name: str, epoch: int) -> None:
        cfg = self.config
        # checkpoint_blocked spans the time this save holds the epoch
        # loop: the whole write for sync formats, only the device->host
        # snapshot under async_save (the writer thread records its own
        # ckpt_background_write span — checkpointing/writer.py).
        tracer = get_tracer()
        mx = get_metrics()
        t0 = tracer.now() if mx.enabled else None
        try:
            with tracer.span(
                "checkpoint_blocked", snapshot=name, epoch=epoch,
                format=cfg.checkpoint_format,
            ):
                if cfg.checkpoint_format == "legacy":
                    save_checkpoint(
                        cfg.checkpoint_dir, payload, acc=self.best_acc,
                        epoch=epoch, name=name,
                        extra=cfg.checkpoint_extra,
                    )
                    return
                if self._ckpt_writer is not None:
                    # Surface an earlier epoch's failed background
                    # write BEFORE starting a new one
                    # (checkpointing/writer.py contract).
                    self._ckpt_writer.check()
                save_sharded(
                    cfg.checkpoint_dir, payload, acc=self.best_acc,
                    epoch=epoch, name=name, extra=cfg.checkpoint_extra,
                    writer=self._ckpt_writer,
                )
        finally:
            if t0 is not None:
                mx.observe(
                    "train_checkpoint_blocked_s", tracer.now() - t0
                )

    def _to_canonical(self, state):
        """Checkpoints are written in the engine's layout-independent
        canonical form when it defines one (e.g. PipelineEngine's
        stage-local packed params -> per-stage pytrees with real layer
        paths), so checkpoints stay interchangeable across engine storage
        layouts and validate per-layer structure on restore."""
        fn = getattr(self.engine, "to_canonical", None)
        return fn(state) if fn is not None else state

    def _from_canonical(self, state):
        fn = getattr(self.engine, "from_canonical", None)
        return fn(state) if fn is not None else state

    def _add(self, sums, metrics):
        """`metrics` onto the running `sums` (None before the first)."""
        if sums is None:
            return metrics
        return add_step_metrics(sums, metrics, self._reductions)

    def _finalize(
        self, sums, n_batches: int, wall: float, data_time: float
    ) -> EpochStats:
        if sums is None or n_batches == 0:
            return EpochStats()
        m = jax.device_get(sums)
        count = float(m["count"])
        return EpochStats(
            loss=float(m["loss_sum"]) / count,
            acc1=100.0 * float(m["correct1"]) / count,
            acc5=100.0 * float(m["correct5"]) / count,
            batch_time=wall / n_batches,
            data_time=data_time / n_batches,
            count=int(count),
            counters={k: float(m[k]) for k in self._reductions},
        )

    def _append_epoch_log(
        self, epoch: int, train: EpochStats, val: EpochStats
    ) -> None:
        """One line per epoch, same fields as the reference's
        `file.write(...)` block (`model_parallel.py:119-125`), plus a JSONL
        twin for machines. Host-0 only (logs are rank-0 artifacts in the
        reference too)."""
        record = {
            "epoch": epoch,
            "train": train.as_dict(),
            "val": val.as_dict(),
            "best_acc": self.best_acc,
        }
        self.history.append(record)
        if not is_primary():
            return
        cfg = self.config
        line = (
            f"epoch {epoch} "
            f"train_loss {train.loss:.4f} train_acc1 {train.acc1:.3f} "
            f"val_loss {val.loss:.4f} val_acc1 {val.acc1:.3f} "
            f"time_per_batch {train.batch_time:.4f} "
            f"time_load_perbatch {train.data_time:.4f}"
        ) + "".join(
            f" {name} {value:g}" for name, value in train.counters.items()
        )
        self._log_print(line)
        if cfg.log_file:
            # An absolute log_file stands alone: log_dir is neither
            # prefixed nor created.
            path = os.path.join(cfg.log_dir, cfg.log_file)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "a") as f:
                f.write(line + "\n")
            with open(os.path.splitext(path)[0] + ".jsonl", "a") as f:
                f.write(json.dumps(record) + "\n")

    @staticmethod
    def _log_print(msg: str) -> None:
        if is_primary():
            print(msg, flush=True)
