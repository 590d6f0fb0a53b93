"""Data-parallel CIFAR-10 training — the reference's `data_parallel.py`
entry point, TPU-native.

Reference surface (`code/distributed_training/data_parallel.py`):
  argparse `--lr` (default 0.4) and `--resume/-r` (`:19-23`); CIFAR-10
  batch 512 train / 1000 test (`:43-51`); MobileNetV2 wrapped in
  `torch.nn.DataParallel` (`:74-78`); SGD(momentum .9, wd 1e-4) +
  CosineAnnealingLR(T_max=90) + LinearWarmup(10) (`:90-96`); 100 epochs
  with best-acc checkpointing and a txt log (`:160-172`).

Here the DataParallel wrapper is a mesh: batch sharded over 'data', params
replicated, gradients all-reduced by XLA — no scatter/replicate/
parallel_apply/gather and no device-0 bottleneck. Run it:

  python -m distributed_model_parallel_tpu.cli.data_parallel --lr 0.4
  python -m distributed_model_parallel_tpu.cli.data_parallel --resume
  python -m distributed_model_parallel_tpu.cli.data_parallel \
      --dataset-type Synthetic --epochs 2 --engine ddp --sync-bn
"""

from __future__ import annotations

import argparse

import jax

from distributed_model_parallel_tpu.cli.common import (
    add_checkpoint_flags,
    add_common_tpu_flags,
    add_grad_reduction_flags,
    build_loaders,
    build_model,
    build_optimizer,
    check_batch_divisibility,
    check_checkpoint_args,
    check_grad_reduction_args,
    compute_dtype_from_flag,
)
from distributed_model_parallel_tpu.parallel.data_parallel import (
    DataParallelEngine,
    DDPEngine,
)
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
    parser = argparse.ArgumentParser(description="TPU CIFAR10 Training")
    # -- the reference's exact flags (`data_parallel.py:19-23`) ----------
    parser.add_argument("--lr", default=0.4, type=float, help="learning rate")
    parser.add_argument("--resume", "-r", action="store_true",
                        help="resume from checkpoint")
    parser.add_argument("--finetune", default=None, metavar="CKPT",
                        help="transplant torch MobileNetV2 weights "
                             "(reference checkpoint format, .pth/.npz; "
                             "module.* prefixes and the {'net': ...} "
                             "wrapper handled) before training — the "
                             "reference's finetune path (Readme.md:200-205)")
    # -- reference hard-codes surfaced as flags --------------------------
    parser.add_argument("-b", "--batch-size", default=512, type=int,
                        help="global batch size (reference: 512)")
    parser.add_argument("--val-batch-size", default=1000, type=int)
    parser.add_argument("--epochs", default=100, type=int)
    parser.add_argument("-type", "--dataset-type", default="CIFAR10",
                        dest="dataset_type")
    parser.add_argument("--data", default="./data", help="dataset path")
    parser.add_argument("--wd", "--weight-decay", default=1e-4, type=float,
                        dest="weight_decay")
    parser.add_argument("--momentum", default=0.9, type=float)
    parser.add_argument("-j", "--workers", default=1, type=int,
                        help="native augmentation thread-pool size")
    # -- TPU-native additions --------------------------------------------
    parser.add_argument("--engine", default="gspmd",
                        choices=("gspmd", "ddp", "fsdp", "tp"),
                        help="gspmd: compiler-partitioned (nn.DataParallel "
                             "equivalent); ddp: explicit shard_map psum "
                             "(DistributedDataParallel equivalent); fsdp: "
                             "params+optimizer sharded 1/N over 'data' "
                             "(ZeRO-3 equivalent); tp: Megatron tensor "
                             "parallelism over a 'model' axis "
                             "(--model-shards; transformer-family models)")
    parser.add_argument("--model-shards", default=1, type=int,
                        help="'model' mesh axis size under --engine tp "
                             "(remaining devices become data-parallel "
                             "replicas)")
    parser.add_argument("--collective-matmul", action="store_true",
                        help="latency-hiding collective matmul under "
                             "--engine tp: run the Megatron projections "
                             "as chunked ppermute rings that overlap "
                             "each ICI hop with the partial dot instead "
                             "of the partitioner's monolithic "
                             "all-gather/reduce-scatter (same math; "
                             "transformer-family models)")
    parser.add_argument("--plan", default=None, metavar="SPEC",
                        help="degenerate ParallelPlan spec for the "
                             "image engines (dpN / fsdpN, ISSUE 19): "
                             "the declarative spelling of --engine "
                             "ddp/fsdp on an N-way data world; "
                             "pp/sp/ep tokens are the LM CLI's "
                             "surface (cli/lm.py --plan)")
    add_grad_reduction_flags(parser)
    add_checkpoint_flags(parser)
    parser.add_argument("--max-restarts", default=0, type=int,
                        help="fail-fast elastic mode: restart from the "
                             "per-epoch checkpoint up to N times on "
                             "failure (0 = off)")
    parser.add_argument("--sync-bn", action="store_true",
                        help="SyncBatchNorm semantics under --engine ddp")
    parser.add_argument("--device-normalize", action="store_true",
                        help="ship uint8 batches and normalize on device "
                             "(4x fewer host->device bytes; same math)")
    parser.add_argument("--device-cache", action="store_true",
                        help="upload the whole dataset to HBM once and "
                             "ship only per-batch INDEX vectors (~2 KB); "
                             "gather+augment+normalize run inside the "
                             "compiled step. For HBM-sized datasets "
                             "(CIFAR); the end-to-end fast path on a "
                             "bandwidth-limited host link")
    add_common_tpu_flags(parser)
    return parser


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.finetune:
        # Fail fast (before datasets/engine/trainer build): typo'd paths
        # or unsupported model families should not cost a download first.
        import os

        if args.resume:
            raise SystemExit(
                "--finetune conflicts with --resume: resume restores the "
                "full training state; drop one of the flags"
            )
        if args.model != "mobilenetv2":
            raise SystemExit(
                "--finetune supports the BN MobileNetV2 ('mobilenetv2'); "
                f"got --model {args.model}"
            )
        if not os.path.exists(args.finetune):
            raise SystemExit(f"--finetune: no such file {args.finetune!r}")
    _plan = None
    if args.plan:
        from distributed_model_parallel_tpu.parallel.plan import (
            parse_plan,
        )

        try:
            _plan = parse_plan(args.plan)
        except ValueError as e:
            raise SystemExit(f"--plan: {e}") from e
        if _plan.pp > 1 or _plan.tp_or_sp > 1 or _plan.ep > 1:
            raise SystemExit(
                f"--plan {_plan.spec}: the image engines run the "
                "data axis only — the plan's pp/sp/ep fields are the "
                "LM CLI's surface (cli/lm.py --plan)"
            )
        want = "fsdp" if _plan.fsdp else "ddp"
        if args.engine not in ("gspmd", want):
            raise SystemExit(
                f"--plan {_plan.spec} spells --engine {want} (plan "
                f"field {'fsdp' if _plan.fsdp else 'dp'}); it "
                f"conflicts with --engine {args.engine} — drop one"
            )
        args.engine = want
    check_grad_reduction_args(args)
    check_checkpoint_args(args)
    from distributed_model_parallel_tpu.cli.common import (
        setup_metrics_out,
    )

    setup_metrics_out(args.metrics_out)  # fail fast on a bad directory
    if args.grad_reduction != "monolithic" and args.engine not in (
        "ddp", "fsdp"
    ):
        raise SystemExit(
            f"--grad-reduction {args.grad_reduction} replaces the "
            "explicit gradient collective of the shard_map engines "
            f"(ddp, fsdp); the declarative --engine {args.engine} step "
            "has no explicit reduction site to bucket or overlap"
        )
    if args.dcn_compression != "none" and args.engine not in (
        "ddp", "fsdp"
    ):
        raise SystemExit(
            "--dcn-compression compresses the explicit cross-slice "
            "gradient hop of the shard_map engines (ddp, fsdp); the "
            f"declarative --engine {args.engine} step has no explicit "
            "'dcn' hop to compress — switch to --engine ddp/fsdp or "
            "drop the flag"
        )
    if args.grad_reduction == "overlapped":
        from distributed_model_parallel_tpu.cli.common import (
            check_overlapped_model,
        )

        check_overlapped_model(args.model, args.overlap_stages)
    if args.engine == "tp" and args.dcn_slices != 1:
        raise SystemExit(
            "--dcn-slices factors the data axis for the hierarchical "
            "reducer; combine it with --engine gspmd/ddp/fsdp, not tp"
        )
    if args.engine != "tp":
        if args.model_shards != 1:
            raise SystemExit(
                "--model-shards sizes the 'model' mesh axis and only "
                "applies under --engine tp"
            )
        if args.collective_matmul:
            raise SystemExit(
                "--collective-matmul decomposes the Megatron TP "
                "projections; it only applies under --engine tp"
            )
    if args.engine == "tp":
        from distributed_model_parallel_tpu.cli.common import (
            TRANSFORMER_MODELS,
        )

        if args.model not in TRANSFORMER_MODELS:
            # MEGATRON_RULES match transformer projection paths only; a
            # CNN under --engine tp would replicate every weight and do
            # redundant compute on the 'model' axis without an error.
            raise SystemExit(
                "--engine tp shards the Megatron projection layers; "
                f"--model {args.model} has none, so every weight would "
                "silently replicate across the 'model' axis (redundant "
                f"compute). Choose one of {', '.join(TRANSFORMER_MODELS)}."
            )
        if args.model_shards < 1:
            raise SystemExit(
                f"--model-shards must be >= 1, got {args.model_shards}"
            )
        if args.collective_matmul and args.model_shards < 2:
            raise SystemExit(
                "--collective-matmul rings over the 'model' axis; a "
                "size-1 ring is a plain dot, so the flag would silently "
                "do nothing — set --model-shards >= 2"
            )
    initialize_backend()
    if _plan is not None and _plan.num_devices != jax.device_count():
        raise SystemExit(
            f"--plan {_plan.spec} factors {_plan.num_devices} "
            f"device(s); this world has {jax.device_count()} — "
            "respell the plan's data axis"
        )
    if args.engine == "tp":
        mesh = make_mesh(MeshSpec(data=-1, model=args.model_shards))
    else:
        mesh = make_mesh(MeshSpec(data=-1, dcn=args.dcn_slices))
    check_batch_divisibility(args.batch_size, mesh)
    check_batch_divisibility(args.val_batch_size, mesh, label="val batch")
    if args.dataset_type == "SyntheticText" and (
        args.device_cache or args.device_normalize
    ):
        raise SystemExit(
            "--device-cache/--device-normalize apply the image "
            "normalize pipeline; token-id datasets ship raw (and are "
            "small on the wire already)"
        )
    itf = None
    if args.device_cache:
        if args.device_normalize:
            raise SystemExit(
                "--device-cache already normalizes on device; "
                "drop --device-normalize"
            )
        from distributed_model_parallel_tpu.cli.common import (
            build_index_loaders,
        )

        train, val, num_classes, itf = build_index_loaders(
            args.dataset_type, args.data, args.batch_size, mesh,
            val_batch_size=args.val_batch_size,
        )
    else:
        train, val, num_classes = build_loaders(
            args.dataset_type, args.data, args.batch_size,
            val_batch_size=args.val_batch_size,
            workers=args.workers,
            device_normalize=args.device_normalize,
        )
    model = build_model(args.model, num_classes, remat=args.remat)
    opt = build_optimizer(args)
    cdt = compute_dtype_from_flag(args.dtype)
    if args.device_normalize:
        from distributed_model_parallel_tpu.cli.common import stats_for
        from distributed_model_parallel_tpu.data.loader import (
            device_normalizer,
        )

        itf = device_normalizer(*stats_for(args.dataset_type))
    if args.engine == "ddp":
        engine = DDPEngine(
            model, opt, mesh, sync_bn=args.sync_bn, compute_dtype=cdt,
            input_transform=itf,
            grad_reduction=args.grad_reduction,
            bucket_mb=args.bucket_mb,
            overlap_stages=args.overlap_stages,
            dcn_compression=args.dcn_compression,
        )
    elif args.engine == "fsdp":
        from distributed_model_parallel_tpu.parallel.fsdp import FSDPEngine

        engine = FSDPEngine(
            model, opt, mesh, compute_dtype=cdt, input_transform=itf,
            grad_reduction=args.grad_reduction,
            bucket_mb=args.bucket_mb,
            overlap_stages=args.overlap_stages,
            dcn_compression=args.dcn_compression,
        )
    elif args.engine == "tp":
        from distributed_model_parallel_tpu.parallel.tensor_parallel import (
            TensorParallelEngine,
        )

        engine = TensorParallelEngine(
            model, opt, mesh, compute_dtype=cdt, input_transform=itf,
            collective_matmul=args.collective_matmul,
        )
    else:
        engine = DataParallelEngine(
            model, opt, mesh, compute_dtype=cdt, input_transform=itf
        )
    checkpoint_dir = args.checkpoint_dir  # one source of truth (cfg + probes)

    def _restart_can_resume() -> bool:
        """Host-0-authoritative: checkpoints are written by host 0 only,
        so on per-host disks every process must adopt host 0's answer or
        the hosts disagree on resume and deadlock in the restore
        broadcast."""
        from distributed_model_parallel_tpu.training.checkpoint import (
            latest_exists,
        )

        exists = latest_exists(checkpoint_dir, "last") or latest_exists(
            checkpoint_dir
        )
        if jax.process_count() > 1:
            import numpy as np
            from jax.experimental import multihost_utils

            exists = bool(int(
                multihost_utils.broadcast_one_to_all(np.int32(exists))
            ))
        return exists

    def make_trainer(restart: bool) -> Trainer:
        resume = args.resume or (restart and _restart_can_resume())
        cfg = TrainerConfig(
            epochs=args.epochs,
            base_lr=args.lr,
            t_max=90,
            warmup_period=10,
            log_file=args.log_file or f"data_para_{args.batch_size}.txt",
            checkpoint_dir=checkpoint_dir,
            resume=resume,
            steps_per_epoch=args.steps_per_epoch,
            steps_per_dispatch=args.steps_per_dispatch,
            profile_dir=args.profile_dir,
            save_last=args.max_restarts > 0,
            checkpoint_format=args.checkpoint_format,
            async_save=args.async_save,
        )
        trainer = Trainer(engine, train, val, cfg, rng=jax.random.PRNGKey(0))
        if args.finetune and not resume:
            from distributed_model_parallel_tpu.models.torch_import import (
                load_torch_checkpoint,
                mobilenetv2_from_torch_state_dict,
            )

            p, s = mobilenetv2_from_torch_state_dict(
                trainer.state.params,
                trainer.state.model_state,
                load_torch_checkpoint(args.finetune),
            )
            # Re-place in the ENGINE'S state layout: _state_sh for the
            # sharded engines (FSDP keeps params/moments 1/N — a
            # replicated put here would materialize the full state on
            # every device, the OOM FSDP exists to avoid); replicated
            # for DP/DDP.
            placement = getattr(engine, "_state_sh", engine._repl)
            trainer.state = jax.device_put(
                trainer.state._replace(params=p, model_state=s),
                placement,
            )
            print(f"==> Transplanted torch weights from {args.finetune}")
        return trainer

    if args.max_restarts > 0:
        from distributed_model_parallel_tpu.training.elastic import (
            elastic_fit,
        )

        out = elastic_fit(
            make_trainer, max_restarts=args.max_restarts,
            checkpoint_dir=checkpoint_dir,
        )
    else:
        out = make_trainer(False).fit()
    from distributed_model_parallel_tpu.cli.common import (
        export_metrics_out,
    )

    export_metrics_out(args.metrics_out)
    return out


if __name__ == "__main__":
    main()
