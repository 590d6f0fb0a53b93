"""Driver of a training cell whose step states some of its arithmetic in
a higher precision than its activations: `train_job`, whole, and one
more comparison behind `correct`.

`train_job` holds the first step's mean loss against the plain
reference's. Where a configuration trains in bfloat16 and states parts
of the step in float32 (a recurrent state carried over the whole
sequence, a router's scores), that loss cannot tell whether they are:
the activations' rounding moves it more than the stated precision
does. So before the warm-up epoch this driver asks the configuration's
builder for `precision_readings(config, reference, params, ids)`:
{name: reading} of the program's stated-precision parts against the
reference's on the SAME inputs, made from the run's first batch and
initial parameters at the sizes the cell times. Each reading is held
to `tolerance[name]` of the configuration, which lies between what the
program reads over its seeds and what the reference reads when it is
computed in the next precision down (both in the configuration file
beside the limit). One reading over its limit makes the run incorrect,
like a loss off the reference's. The readings run once, on the device,
beside the training state and before any step: set-up, not window.
"""

from __future__ import annotations

import tempfile

from benchmark.drivers import train_job
from benchmark.drivers.train_job import rehearse  # noqa: F401
from benchmark.harness import manifest
from benchmark.harness.progress import progress


class Window(train_job.Window):
    def run(self, trainer) -> None:
        trainer.train_loader.set_epoch(0)
        ids, _ = next(iter(trainer.train_loader))
        placed_ids, _ = trainer.engine.shard_batch(ids, ids)
        readings = self.builder.precision_readings(
            self.cell.config, self.reference, trainer.state.params,
            placed_ids,
        )
        progress(self.t_process, f"stated precision {readings}")
        super().run(trainer)
        limits = {
            name: self.cell.config["tolerance"][name] for name in readings
        }
        for name, value in readings.items():
            if not value <= limits[name]:
                self.record["notes"].append(
                    f"{name} reads {value:.3g} from the reference, over "
                    f"its limit of {limits[name]:g}"
                )
        self.record["correct"] = not self.record["notes"]
        self.record["check"]["precision"] = readings
        self.record["check"]["precision_limits"] = limits


def run(cell, args, t_process: float) -> dict:
    from distributed_model_parallel_tpu.cli import lm

    config = cell.config
    builder = manifest.load_module("builder", config["builder"])
    reference = manifest.load_module("reference", config["reference"])
    window = Window(cell, args, t_process, builder, reference)
    base = lm.Trainer
    lm.Trainer = train_job.bench_trainer(base, window)
    try:
        with tempfile.TemporaryDirectory(prefix="bench_train_") as out_dir:
            lm.main(builder.lm_argv(config, cell.traffic, args.seed, out_dir))
    finally:
        lm.Trainer = base
    return window.record
