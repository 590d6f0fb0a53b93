"""Driver of the training cells: a steady pretraining job through the
program's own entry point and loop, `cli.lm.main` + `Trainer.train_epoch`.

`cli.lm.main` builds the mesh, the engine, the corpus and the loaders
from its command line, exactly as a user's run does; the only thing
replaced is `Trainer`, by a subclass that makes the state in one jitted
call from the seed and whose `fit` is the benchmark's window: the
step-0 loss against the plain reference, one warm-up epoch (which
compiles), then whole `train_epoch` calls until `--seconds` are spent.
Each epoch ends in the Trainer's own value fetch, so an epoch's wall
time is the device's, with the input pipeline running.

What a user's run does and this one does not: the program's eager
`init_state` (the benchmark's jitted one stands in, as the contract
asks of weights; at GPT-2 XL the program's cannot run at all, so that
cell measures a job `cli.lm` alone cannot start today — the
configuration's `assumed.training_state` says so), validation, and the
best-accuracy snapshot.
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
import functools
import math
import tempfile
import time

from benchmark.harness import flops, manifest, spans
from benchmark.harness.device import (
    CompileCounter,
    memory_peak_bytes,
    seed_key,
)
from benchmark.harness.progress import progress

# Toy rehearsal (CPU, tests only).
REHEARSAL = {"corpus_tokens": 8192, "steps_per_epoch": 2}


def rehearse(traffic: dict) -> dict:
    out = copy.deepcopy(traffic)
    out.update(REHEARSAL)
    return out


class Window:
    """What `fit` does in place of the Trainer's epoch-and-validate
    loop, and what it found."""

    def __init__(self, cell, args, t_process: float, sizes: dict,
                 reference):
        self.cell = cell
        self.args = args
        self.t_process = t_process
        self.sizes = sizes
        self.reference = reference
        self.record: dict = {}

    def reference_loss(self, trainer) -> float:
        """The reference's mean next-token loss on the first batch the
        Trainer will train on, with the parameters as initialised. The
        whole batch, not a few sequences of it: the train step reports
        one loss for its batch, and that is what this is held against.
        The batch is placed as the engine places it, so on several chips
        the reference is partitioned along the batch like the step
        itself (its arithmetic stays float32 at `highest`)."""
        import jax

        trainer.train_loader.set_epoch(0)
        ids, _ = next(iter(trainer.train_loader))
        placed_ids, _ = trainer.engine.shard_batch(ids, ids)
        total, count = jax.jit(functools.partial(
            self.reference.next_token_loss, num_heads=self.sizes["n_head"]
        ))(trainer.state.params, placed_ids)
        return float(total) / float(count)

    def run(self, trainer) -> None:
        cell, args = self.cell, self.args
        training = cell.config["training"]
        steps = cell.traffic["steps_per_epoch"]
        if cell.traffic["lr_schedule"] != "constant":
            raise NotImplementedError(cell.traffic["lr_schedule"])
        lr = trainer.config.base_lr
        trainer.lr_fn = lambda epoch: lr

        progress(self.t_process, "cli.lm built the job, state made")
        ref_loss = self.reference_loss(trainer)
        progress(self.t_process, f"reference loss {ref_loss:.5f}")
        # The first train step's own metrics (no fence added: they are
        # read after the warm-up epoch's value fetch).
        engine = trainer.engine
        step = engine.train_step
        first = {}

        def first_step(*step_args):
            engine.train_step = step
            state, metrics = step(*step_args)
            first.update(metrics)
            return state, metrics

        engine.train_step = first_step
        warm = trainer.train_epoch(0)
        step0_loss = float(first["loss_sum"]) / float(first["count"])
        progress(self.t_process, f"warm-up epoch done, step-0 loss "
                                 f"{step0_loss:.5f}, epoch loss {warm.loss:.4f}")
        tol = cell.config["tolerance"]["train_loss"]

        compiles = CompileCounter()
        host = spans.HostSpans() if args.trace else None
        device_trace = None
        epochs = []
        with spans.trace_dir() as tdir:
            compiles.start()
            t_start = time.perf_counter()
            # The first measured epoch carries the device trace and is
            # left out of the rates; at least one untraced epoch runs.
            while (time.perf_counter() - t_start < args.seconds
                   or not any(not e["traced"] for e in epochs)):
                traced = bool(args.trace) and not epochs
                with (spans.profiled(tdir) if traced
                      else contextlib.nullcontext()):
                    t0 = time.perf_counter()
                    stats = trainer.train_epoch(len(epochs) + 1)
                    wall = time.perf_counter() - t0
                epochs.append({
                    "steps": steps, "wall_s": wall, "loss": stats.loss,
                    "data_s": stats.data_time * steps, "traced": traced,
                })
            compiles.stop()
            window_s = time.perf_counter() - t_start
            progress(self.t_process, f"{len(epochs)} measured epochs in "
                                     f"{window_s:.1f}s")
            if args.trace:
                device_trace = spans.reduce_dir(tdir, host.collect())

        losses = [warm.loss] + [e["loss"] for e in epochs]
        bad_steps = steps * sum(1 for x in losses if not math.isfinite(x))
        notes = []
        if not abs(step0_loss - ref_loss) <= tol:
            notes.append(
                f"step-0 loss {step0_loss:.5f} is {step0_loss - ref_loss:+.5f}"
                f" from the reference's {ref_loss:.5f} (tolerance {tol})"
            )
        if bad_steps:
            notes.append(f"non-finite loss: {losses}")
        elif not losses[-1] < losses[0]:
            notes.append(f"the loss did not fall: {losses}")
        if compiles.count:
            notes.append(f"{compiles.count} programs compiled or loaded "
                         "inside the measured window")
        self.record = {
            "setup_s": t_start - self.t_process,
            "window_s": window_s,
            "attempted": steps * len(losses),
            "failed": bad_steps,
            "correct": not notes,
            "notes": notes,
            "check": {"step0_loss": step0_loss, "reference_loss": ref_loss,
                      "loss_tol": tol, "epoch_losses": losses},
            "compiles_in_window": compiles.count,
            "memory_peak_bytes": memory_peak_bytes(),
            "epochs": epochs,
            "tokens_per_step": training["batch_size"] * training["seq_len"],
            "train_flops_per_token": flops.train_flops_per_token(
                self.sizes, training["seq_len"]
            ),
            "shape": self.sizes,
            "device_trace": device_trace,
        }


def sharded_init(engine):
    """`engine.init_state` as one jitted call whose results are born in
    the engine's layout: the layout its sharded-checkpoint seam states
    (`state_partition_specs`), or replicated over its mesh where it has
    none. The program's own eager `init_state` builds every leaf whole
    on chip 0 first, which GPT-2 XL's 19.7 GB of state cannot survive."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    specs = getattr(engine, "state_partition_specs", None)
    if specs is None:
        layout = NamedSharding(engine.mesh, PartitionSpec())
    else:
        layout = jax.tree_util.tree_map(
            lambda spec: NamedSharding(engine.mesh, spec), specs(),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
    return jax.jit(engine.init_state, out_shardings=layout)


def bench_trainer(base, window: Window):
    """`base` (the program's Trainer) with the benchmark's state and
    window; `train_epoch`, the loop under test, is inherited."""
    import jax

    class BenchTrainer(base):
        def __init__(self, engine, train_loader, val_loader, config,
                     rng=None):
            engine.init_state = sharded_init(engine)
            super().__init__(
                engine, train_loader, None,
                # no validation, no snapshot: neither is the job's speed
                dataclasses.replace(config, save_best=False),
                rng=seed_key(window.args.seed),
            )

        def fit(self) -> dict:
            window.run(self)
            return {"best_acc": 0.0, "epochs": 0, "history": []}

    return BenchTrainer


def run(cell, args, t_process: float) -> dict:
    from distributed_model_parallel_tpu.cli import lm

    config = cell.config
    builder = manifest.load_module("builder", config["builder"])
    reference = manifest.load_module("reference", config["reference"])
    window = Window(cell, args, t_process, builder.shape(config), reference)
    base = lm.Trainer
    lm.Trainer = bench_trainer(base, window)
    try:
        with tempfile.TemporaryDirectory(prefix="bench_train_") as out_dir:
            lm.main(builder.lm_argv(config, cell.traffic, args.seed, out_dir))
    finally:
        lm.Trainer = base
    return window.record
