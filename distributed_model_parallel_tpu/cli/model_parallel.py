"""Pipeline model-parallel training — the reference's `model_parallel.py`
entry point, TPU-native.

Reference surface (`code/distributed_training/model_parallel.py:15-42`):
positional `data`, `--dist-url`, `--world-size`, `--dist-backend`, `--lr`,
`--epochs`, `-type/--dataset-type`, `-b`, `-j/--workers`, `--wd`,
`--momentum`. It forks one process per rank (`:160-163`), splits
MobileNetV2 by rank (`:99-157`) and moves activations with NCCL P2P.

Here `--world-size N` becomes N pipeline stages on the 'stage' axis of one
SPMD mesh (remaining devices become data-parallel pipeline replicas);
`--dist-url` is only needed for explicit multi-host rendezvous
(`jax.distributed.initialize`), and `--dist-backend` accepts 'xla' (the
only backend; 'nccl' is tolerated and mapped to 'xla' so reference launch
lines keep working). Run it:

  python -m distributed_model_parallel_tpu.cli.model_parallel ./data \
      --world-size 4 --lr 0.4 -b 512
  python -m distributed_model_parallel_tpu.cli.model_parallel ./data \
      -type Synthetic --world-size 4 --microbatches 8 --epochs 2
"""

from __future__ import annotations

import argparse

import jax

from distributed_model_parallel_tpu.cli.common import (
    STAGE_BUILDERS,
    add_common_tpu_flags,
    build_loaders,
    build_optimizer,
    check_batch_divisibility,
    check_pipeline_schedule_args,
    compute_dtype_from_flag,
)
from distributed_model_parallel_tpu.parallel.pipeline import PipelineEngine
from distributed_model_parallel_tpu.runtime.dist import initialize_backend
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.runtime.platform import (
    enable_compile_cache,
)
from distributed_model_parallel_tpu.training.trainer import (
    Trainer,
    TrainerConfig,
)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="TPU Pipeline Training")
    # -- the reference's exact flags (`model_parallel.py:15-42`) ---------
    parser.add_argument("data", metavar="DIR", help="path to dataset")
    parser.add_argument("--dist-url", default=None, type=str,
                        help="coordinator address for explicit multi-host "
                             "rendezvous (host:port); default autodiscovers")
    parser.add_argument("--world-size", default=1, type=int,
                        help="number of pipeline stages (reference: number "
                             "of ranks)")
    parser.add_argument("--dist-backend", default="xla", type=str,
                        choices=("xla", "nccl"),
                        help="'nccl' is accepted for launch-line "
                             "compatibility and mapped to 'xla'")
    parser.add_argument("--lr", "--learning-rate", default=0.4, type=float,
                        dest="lr")
    parser.add_argument("--epochs", default=90, type=int)
    parser.add_argument("-type", "--dataset-type", default="Imagenet",
                        dest="dataset_type")
    parser.add_argument("-b", "--batch-size", default=512, type=int)
    parser.add_argument("-j", "--workers", default=12, type=int,
                        help="native augmentation thread-pool size "
                             "(reference `-j`); batches are staged ahead "
                             "by the loader's prefetch thread either way")
    parser.add_argument("--wd", "--weight-decay", default=1e-4, type=float,
                        dest="weight_decay")
    parser.add_argument("--momentum", default=0.9, type=float)
    # -- TPU-native additions --------------------------------------------
    parser.add_argument("--microbatches", default=1, type=int,
                        help="pipeline microbatches in flight; 1 = the "
                             "reference's single-batch schedule")
    parser.add_argument("--pipeline-schedule", default="gpipe",
                        choices=("gpipe", "1f1b", "interleaved"),
                        help="gpipe = fill-drain (O(M) live activations); "
                             "1f1b = one-forward-one-backward "
                             "(PipeDream-flush), same trajectory with "
                             "O(S) live activations — lets "
                             "--microbatches scale until the bubble is "
                             "negligible; interleaved = Megatron's "
                             "virtual pipeline (pair with "
                             "--virtual-stages V): same trajectory with "
                             "the bubble floor divided by V")
    parser.add_argument("--virtual-stages", default=1, type=int,
                        help="model chunks per pipeline stage "
                             "(interleaved schedule): the model splits "
                             "into world-size x V chunks and device s "
                             "owns chunks s, s+S, ... — bubble fraction "
                             "drops from (S-1)/(M+S-1) to "
                             "(S-1)/(V*M+S-1); needs --microbatches "
                             "divisible by --world-size")
    parser.add_argument("--reference-split", action="store_true",
                        help="use the reference's exact ws=4 stage "
                             "boundaries [3, 9, 15] (requires "
                             "--world-size 4, MobileNetV2)")
    parser.add_argument("--stage-local-params", action="store_true",
                        help="store params/optimizer sharded over 'stage' "
                             "(each device holds ~1/S of the model) "
                             "instead of replicated")
    add_common_tpu_flags(parser)
    return parser


def build_stages(model: str, num_stages: int, num_classes: int,
                 reference_split: bool, virtual_stages: int = 1):
    """[Layer] chunks for the pipeline engine: `num_stages` devices ×
    `virtual_stages` chunks each (the interleaved schedule's S·V split;
    V=1 is the classic one-stage-per-device partition)."""
    boundaries = None
    if reference_split:
        if virtual_stages != 1:
            raise SystemExit(
                "--reference-split fixes the ws=4 one-chunk-per-rank "
                "boundaries [3, 9, 15]; it cannot be combined with "
                "--virtual-stages > 1 (which needs a 4*V-way split)"
            )
        if num_stages != 4 or not model.startswith("mobilenetv2"):
            raise SystemExit(
                "--reference-split needs --world-size 4 and MobileNetV2"
            )
        boundaries = [3, 9, 15]
    if model not in STAGE_BUILDERS:
        raise SystemExit(
            f"model {model!r} has no pipeline stage builder; "
            f"pipeline-splittable models: {sorted(STAGE_BUILDERS)}. "
            f"(Every model trains under the data-parallel CLI.)"
        )
    try:
        return STAGE_BUILDERS[model](
            num_stages * virtual_stages, num_classes, boundaries
        )
    except ValueError as e:
        # split_points rejects more chunks than blocks — surface it in
        # CLI-flag vocabulary.
        raise SystemExit(
            f"model {model!r} cannot split into "
            f"{num_stages * virtual_stages} chunks (--world-size "
            f"{num_stages} x --virtual-stages {virtual_stages}): {e}"
        )


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    check_pipeline_schedule_args(
        args.pipeline_schedule, args.virtual_stages, args.microbatches,
        args.world_size,
    )
    from distributed_model_parallel_tpu.cli.common import (
        setup_metrics_out,
    )

    setup_metrics_out(args.metrics_out)
    initialize_backend(coordinator_address=args.dist_url)
    mesh = make_mesh(MeshSpec(data=-1, stage=args.world_size))
    check_batch_divisibility(
        args.batch_size, mesh, microbatches=args.microbatches
    )
    train, val, num_classes = build_loaders(
        args.dataset_type, args.data, args.batch_size,
        workers=args.workers,
    )
    stages = build_stages(
        args.model, args.world_size, num_classes, args.reference_split,
        args.virtual_stages,
    )
    engine = PipelineEngine(
        stages,
        build_optimizer(args),
        mesh,
        num_microbatches=args.microbatches,
        compute_dtype=compute_dtype_from_flag(args.dtype),
        stage_local_params=args.stage_local_params,
        remat=args.remat,
        schedule=args.pipeline_schedule,
        virtual_stages=args.virtual_stages,
    )
    cfg = TrainerConfig(
        epochs=args.epochs,
        base_lr=args.lr,
        t_max=90,
        warmup_period=10,
        log_file=args.log_file or f"{args.batch_size}.txt",
        steps_per_epoch=args.steps_per_epoch,
        steps_per_dispatch=args.steps_per_dispatch,
        profile_dir=args.profile_dir,
    )
    trainer = Trainer(engine, train, val, cfg, rng=jax.random.PRNGKey(0))
    out = trainer.fit()
    from distributed_model_parallel_tpu.cli.common import (
        export_metrics_out,
    )

    export_metrics_out(args.metrics_out)
    return out


if __name__ == "__main__":
    main()
