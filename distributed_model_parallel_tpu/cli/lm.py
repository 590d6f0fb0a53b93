"""Causal-LM pretraining entry point — the text-side third launcher.

The reference trains CNN classifiers only; this CLI completes the
framework's transformer surface: GPT-family next-token pretraining on a
(data × seq) mesh, driven by the same Trainer epoch protocol (loss /
acc1 / acc5-as-next-token-metrics, batch timing, txt+JSONL logs,
best-"acc" checkpointing) the image CLIs use.

`--seq-shards N` turns on ring/Ulysses context parallelism
(`parallel/sequence_parallel.CausalLMSequenceParallelEngine`); N=1 is
plain data parallelism through the same engine (a 1-shard ring is the
identity). The corpus is the deterministic Markov-chain synthetic
stream (`data/lm.py` — this sandbox has no text datasets); its
conditional entropy is printed as the loss floor so convergence is
interpretable.

  python -m distributed_model_parallel_tpu.cli.lm \
      --dim 128 --layers 4 --heads 4 --seq-len 256 -b 32 \
      --epochs 5 --lr 3e-4
  python -m distributed_model_parallel_tpu.cli.lm --seq-shards 4 \
      --attention ring --dtype bfloat16
  python -m distributed_model_parallel_tpu.cli.lm --moe-experts 8 \
      --moe-dispatch hierarchical --moe-overlap --dcn-slices 2
  python -m distributed_model_parallel_tpu.cli.lm --model-config \
      kimi-linear.json --seq-len 8192 -b 2 --attention ring_flash \
      --dtype bfloat16 --remat
"""

from __future__ import annotations

import argparse

import jax

from distributed_model_parallel_tpu.cli.common import (
    add_checkpoint_flags,
    add_grad_reduction_flags,
    build_optimizer,
    check_batch_divisibility,
    check_checkpoint_args,
    check_grad_reduction_args,
    check_pipeline_schedule_args,
    compute_dtype_from_flag,
)
from distributed_model_parallel_tpu.data.lm import (
    LMLoader,
    chain_entropy,
    synthetic_corpus,
)
from distributed_model_parallel_tpu.models.gpt import GPTConfig
from distributed_model_parallel_tpu.parallel.sequence_parallel import (
    CausalLMSequenceParallelEngine,
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
    p = argparse.ArgumentParser(description="TPU causal-LM pretraining")
    p.add_argument("--vocab-size", default=256, type=int)
    p.add_argument("--dim", default=128, type=int)
    p.add_argument("--layers", default=4, type=int)
    p.add_argument("--heads", default=4, type=int)
    p.add_argument("--ffn-dim", default=None, type=int,
                   help="default 4*dim")
    p.add_argument("--model-config", default=None, metavar="FILE",
                   help="build the model family named by the file's "
                        "`model_type` from the source's own keys (a "
                        "release's config.json; `experts_held: [first, "
                        "past_last]` beside them for a chip that holds "
                        "a range of the experts) in place of the GPT "
                        "shape flags; trains "
                        "under the sequence-parallel engine with "
                        "--seq-shards 1")
    p.add_argument("--seq-len", default=256, type=int)
    p.add_argument("--dropout", default=0.0, type=float)
    p.add_argument("-b", "--batch-size", default=32, type=int)
    p.add_argument("--epochs", default=5, type=int)
    p.add_argument("--lr", default=3e-4, type=float)
    p.add_argument("--optimizer", default="adamw",
                   choices=("sgd", "adamw"),
                   help="LM convention: adamw (sgd kept for parity runs)")
    p.add_argument("--wd", "--weight-decay", default=1e-2, type=float,
                   dest="weight_decay")
    p.add_argument("--momentum", default=0.9, type=float)
    p.add_argument("--corpus-tokens", default=1 << 16, type=int)
    p.add_argument("--corpus-seed", default=0, type=int)
    p.add_argument("--seq-shards", default=1, type=int,
                   help="'seq' mesh axis size (context parallelism); "
                        "1 = plain data parallelism")
    p.add_argument("--pipeline-stages", default=1, type=int,
                   help="pipeline-parallel LM over the 'stage' axis "
                        "(models/gpt.py split_stages + LMPipelineEngine);"
                        " mutually exclusive with --seq-shards > 1")
    p.add_argument("--microbatches", default=1, type=int,
                   help="pipeline microbatches (pipeline mode)")
    p.add_argument("--pipeline-schedule", default="gpipe",
                   choices=("gpipe", "1f1b", "interleaved"),
                   help="pipeline schedule (pipeline mode): gpipe = "
                        "fill-drain, O(M) live activations; 1f1b = "
                        "PipeDream-flush, O(S) — same trajectory; "
                        "interleaved = Megatron virtual pipeline (pair "
                        "with --virtual-stages V) — same trajectory, "
                        "bubble floor divided by V")
    p.add_argument("--virtual-stages", default=1, type=int,
                   help="decoder-block chunks per pipeline stage "
                        "(interleaved schedule): the model splits into "
                        "--pipeline-stages x V chunks dealt round-robin "
                        "to devices; needs --microbatches divisible by "
                        "--pipeline-stages and --layers >= S*V")
    p.add_argument("--attention", default="ring",
                   choices=("ring", "ring_flash", "ulysses",
                            "ulysses_flash"),
                   help="*_flash = Pallas kernels as the attention core "
                        "(the long-context hot paths on TPU)")
    p.add_argument("--moe-experts", default=0, type=int,
                   help="Mixture-of-Experts: swap the FFN of every "
                        "--moe-every-th decoder block for a routed MoE "
                        "with this many experts (models/moe.py) and "
                        "train under the expert-parallel LM engine; "
                        "0 = dense (default)")
    p.add_argument("--moe-every", default=2, type=int,
                   help="which decoder blocks are MoE (1 = every "
                        "layer, 2 = every other, ...)")
    p.add_argument("--moe-dispatch", default="gspmd",
                   choices=("gspmd", "hierarchical"),
                   help="MoE token exchange: gspmd = experts sharded "
                        "over an --expert-shards 'expert' mesh axis, "
                        "flat all-to-all from the partitioner; "
                        "hierarchical = experts ride the (--dcn-slices "
                        "factored) data fabric through the explicit "
                        "two-level moe_ring exchange — intra-slice "
                        "all-to-all over 'ici', ONE cross-slice "
                        "exchange on the 1/ici shard "
                        "(ops/expert_dispatch.py)")
    p.add_argument("--moe-overlap", action="store_true",
                   help="chunk the hierarchical exchange so expert FFN "
                        "compute on chunk k hides the communication of "
                        "chunk k+1 (requires --moe-dispatch "
                        "hierarchical; same math)")
    p.add_argument("--expert-shards", default=1, type=int,
                   help="'expert' mesh axis size (gspmd dispatch); "
                        "hierarchical dispatch shards experts over the "
                        "data fabric instead and requires 1")
    p.add_argument("--collective-matmul", action="store_true",
                   help="latency-hiding collective matmul (seq-parallel "
                        "mode): run each block's FFN pair as chunked "
                        "ppermute rings over 'seq' — every ICI hop "
                        "overlaps the partial dot already on hand "
                        "(same math; requires --ffn-dim divisible by "
                        "--seq-shards)")
    p.add_argument("--plan", default=None, metavar="SPEC",
                   help="composed ParallelPlan spec (parallel/plan.py, "
                        "ISSUE 19/20): one declarative mesh "
                        "factorization — tokens ppN/spN/dpN/fsdpN "
                        "joined by 'x', e.g. pp2xsp2xdp2 or fsdp8; the "
                        "pp token takes a schedule suffix (pp2-1f1b, "
                        "pp4-int2 for interleaved with V=2 virtual "
                        "stages; default gpipe) — driven through "
                        "build_plan_engine (degenerate specs route to "
                        "the single-axis engines). Replaces the "
                        "per-axis flags (--pipeline-stages, "
                        "--seq-shards)")
    add_grad_reduction_flags(p)
    add_checkpoint_flags(p)
    p.add_argument("--dtype", default="float32",
                   choices=("float32", "bfloat16"))
    p.add_argument("--remat", action="store_true")
    p.add_argument("--steps-per-epoch", default=0, type=int)
    p.add_argument("--steps-per-dispatch", default=1, type=int,
                   help="fold N optimizer steps into one compiled "
                        "dispatch (lax.scan; trajectory-identical)")
    p.add_argument("--log-file", default=None)
    p.add_argument("--profile-dir", default=None)
    p.add_argument("--resume", "-r", action="store_true")
    from distributed_model_parallel_tpu.cli.common import (
        add_metrics_out_flag,
    )

    add_metrics_out_flag(p)
    return p


# `model_type` of a --model-config file -> its configuration's builder.
def _model_families() -> dict:
    from distributed_model_parallel_tpu.models import kimi_linear

    return {kimi_linear.MODEL_TYPE: kimi_linear.config_from_dict}


# What --model-config replaces or cannot be combined with, by flag.
_MODEL_CONFIG_SHAPE_FLAGS = (
    ("--vocab-size", "vocab_size"), ("--dim", "dim"),
    ("--layers", "layers"), ("--heads", "heads"),
    ("--ffn-dim", "ffn_dim"), ("--dropout", "dropout"),
    ("--moe-experts", "moe_experts"), ("--moe-every", "moe_every"),
    ("--moe-dispatch", "moe_dispatch"), ("--moe-overlap", "moe_overlap"),
    ("--expert-shards", "expert_shards"),
)


def _model_config(args, parser):
    """The configuration `--model-config FILE` names, after the guards:
    the file carries the shape, so the GPT shape flags and the GPT MoE
    flags beside it are refused by name, and so is every engine that
    has no path for the family yet."""
    import json

    for flag, dest in _MODEL_CONFIG_SHAPE_FLAGS:
        if getattr(args, dest) != parser.get_default(dest):
            raise SystemExit(
                f"{flag} shapes the GPT family; --model-config "
                f"{args.model_config} carries the model's shape — drop "
                "the flag"
            )
    with open(args.model_config) as f:
        described = json.load(f)
    families = _model_families()
    model_type = described.get("model_type")
    if model_type not in families:
        raise SystemExit(
            f"--model-config {args.model_config}: model_type "
            f"{model_type!r} is not built (have: "
            f"{', '.join(sorted(families))})"
        )
    try:
        cfg = families[model_type](described)
    except (KeyError, NotImplementedError, ValueError) as e:
        raise SystemExit(f"--model-config {args.model_config}: {e}") from e
    missing = cfg.lm_family().seq_shards_missing
    for flag, on, why in (
        ("--seq-shards", args.seq_shards > 1, missing),
        ("--plan", bool(args.plan),
         "build_plan_engine composes GPT stages; " + (missing or "")),
        ("--pipeline-stages", args.pipeline_stages > 1,
         "models/gpt.split_stages cuts a GPT stack; this family's mixed "
         "layer pattern has no stage split"),
        ("--grad-reduction overlapped",
         args.grad_reduction == "overlapped",
         "the stagewise backward carries no block state"),
    ):
        if on and why:
            raise SystemExit(
                f"--model-config {args.model_config} ({model_type}) does "
                f"not compose with {flag}: {why}"
            )
    return cfg


def main(argv=None) -> dict:
    parser = build_parser()
    args = parser.parse_args(argv)
    enable_compile_cache()
    from distributed_model_parallel_tpu.cli.common import (
        setup_metrics_out,
    )

    setup_metrics_out(args.metrics_out)  # fail fast on a bad directory
    initialize_backend()
    model_cfg = None
    if args.model_config:
        # Before every other guard: the conflict named is this one.
        model_cfg = _model_config(args, parser)
        args.vocab_size = model_cfg.vocab_size  # the corpus draws from it
    plan = None
    if args.plan:
        from distributed_model_parallel_tpu.parallel.plan import (
            parse_plan,
        )

        try:
            plan = parse_plan(args.plan)
        except ValueError as e:
            raise SystemExit(f"--plan: {e}") from e
        if args.pipeline_stages > 1 or args.seq_shards > 1:
            raise SystemExit(
                f"--plan {plan.spec} IS the mesh factorization; it "
                "composes with neither --pipeline-stages nor "
                "--seq-shards (the plan's pp/sp fields replace them) "
                "— drop the per-axis flags"
            )
        if args.pipeline_schedule != "gpipe" or args.virtual_stages != 1:
            raise SystemExit(
                f"plan {plan.spec}: ParallelPlan.schedule rides the "
                "pp token's suffix (--plan pp2-1f1b, pp4-int2); "
                "--pipeline-schedule and --virtual-stages ride "
                "--pipeline-stages, not --plan — drop the flags and "
                "spell the schedule in the spec"
            )
        if args.microbatches != 1 and plan.pp <= 1:
            raise SystemExit(
                f"--microbatches schedules the plan's pipeline axis, "
                f"but plan {plan.spec} has pp=1 — add a ppN token or "
                "drop the flag"
            )
        if plan.ep > 1:
            raise SystemExit(
                f"plan {plan.spec}: the CLI's expert surface is "
                "--moe-experts/--moe-dispatch (experts ride the data "
                "fabric); the plan's ep field is the engine's "
                "surface — drop the ep token"
            )
        if args.moe_experts > 0:
            raise SystemExit(
                f"--moe-experts trains under the expert-parallel "
                f"engine, but plan {plan.spec} has ParallelPlan.ep=1 "
                "and ep composition is not built — drop --plan or "
                "--moe-experts"
            )
        if args.attention != "ring" and plan.tp_or_sp <= 1:
            raise SystemExit(
                f"--attention selects the 'seq'-axis distribution, "
                f"but plan {plan.spec} has sp=1 (stages attend "
                "locally; the engine picks the kernel) — add an spN "
                "token or drop the flag"
            )
        if args.collective_matmul and plan.tp_or_sp <= 1:
            raise SystemExit(
                f"--collective-matmul rings over the plan's 'seq' "
                f"axis, but plan {plan.spec} has sp=1 — add an spN "
                "token or drop the flag"
            )
        if args.dcn_slices != 1:
            raise SystemExit(
                f"--dcn-slices factors the data axis for the "
                "hierarchical reducer; the stage-major plan mesh "
                f"(plan {plan.spec}) lays its pp field across the "
                "slice boundary by construction — drop the flag"
            )
        if (
            args.grad_reduction != "monolithic"
            or args.dcn_compression != "none"
            or args.bucket_mb is not None
            or args.overlap_stages is not None
        ):
            raise SystemExit(
                f"plan {plan.spec} reduces gradients with ONE fused "
                "psum over ('stage','data','seq'); the "
                "--grad-reduction/--bucket-mb/--overlap-stages/"
                "--dcn-compression knobs ride the single-axis "
                "engines — drop the flags or --plan"
            )
    if args.pipeline_stages > 1 and args.seq_shards > 1:
        raise SystemExit(
            "--pipeline-stages and --seq-shards are mutually exclusive "
            "(one engine per run; compose data parallelism with either)"
        )
    if args.pipeline_stages > 1 and args.collective_matmul:
        raise SystemExit(
            "--collective-matmul decomposes the sequence-parallel "
            "engine's FFN collectives; it has no effect under "
            "--pipeline-stages (stages compute dense locally)"
        )
    if args.collective_matmul and args.seq_shards < 2 and plan is None:
        # Under --plan the sp-field guard above already ruled (a plan
        # with sp >= 2 carries a real 'seq' ring for the cm chunks).
        raise SystemExit(
            "--collective-matmul rings over the 'seq' axis; a size-1 "
            "ring is a plain dot, so the flag would silently do "
            "nothing — set --seq-shards >= 2"
        )
    if args.pipeline_stages > 1 and args.attention != "ring":
        # The --attention choices are 'seq'-axis DISTRIBUTION patterns;
        # pipeline stages attend locally (dense causal). Silently
        # training dense while the flag promises a flash kernel would
        # mislabel every number the run produces.
        raise SystemExit(
            "--attention selects the sequence-parallel distribution "
            "and has no effect under --pipeline-stages (stages attend "
            "locally, dense causal); drop the flag"
        )
    if (args.pipeline_stages <= 1 and args.microbatches != 1
            and plan is None):
        # A plan with pp > 1 accepts --microbatches (the composed tick
        # loop's M); the plan block above rules the pp=1 case.
        raise SystemExit(
            "--microbatches is a pipeline-schedule knob; it has no "
            "effect without --pipeline-stages > 1"
        )
    if (args.pipeline_stages <= 1 and args.pipeline_schedule != "gpipe"
            and plan is None):
        raise SystemExit(
            "--pipeline-schedule selects the pipeline engine's tick "
            "program; it has no effect without --pipeline-stages > 1"
        )
    if (args.pipeline_stages <= 1 and args.virtual_stages != 1
            and plan is None):
        raise SystemExit(
            "--virtual-stages is an interleaved-pipeline knob; it has "
            "no effect without --pipeline-stages > 1"
        )
    if args.microbatches < 1:
        raise SystemExit(
            f"--microbatches must be >= 1, got {args.microbatches}"
        )
    if args.moe_experts < 0:
        raise SystemExit(
            f"--moe-experts must be >= 0, got {args.moe_experts}"
        )
    if args.moe_experts == 0:
        for flag, bad in (
            ("--moe-dispatch", args.moe_dispatch != "gspmd"),
            ("--moe-overlap", args.moe_overlap),
            ("--expert-shards", args.expert_shards != 1),
            ("--moe-every", args.moe_every != 2),
        ):
            if bad:
                raise SystemExit(
                    f"{flag} configures the MoE expert exchange; it "
                    "has no effect without --moe-experts > 0"
                )
    else:
        if args.seq_shards > 1 or args.pipeline_stages > 1:
            raise SystemExit(
                "--moe-experts trains under the expert-parallel LM "
                "engine (GSPMD data x expert); it composes with "
                "neither --seq-shards > 1 nor --pipeline-stages > 1 — "
                "per-shard routing would break the dense capacity "
                "semantics"
            )
        if args.collective_matmul:
            raise SystemExit(
                "--collective-matmul rings over the 'seq' axis of the "
                "sequence-parallel engine; it has no effect under "
                "--moe-experts"
            )
        if args.attention != "ring":
            # Same principle as the pipeline branch: --attention picks
            # a 'seq'-axis distribution pattern; the MoE LM attends
            # dense causal, and silently training dense while the flag
            # promises a flash kernel would mislabel every number.
            raise SystemExit(
                "--attention selects the sequence-parallel "
                "distribution and has no effect under --moe-experts "
                "(the MoE LM attends locally, dense causal); drop the "
                "flag"
            )
        if args.grad_reduction != "monolithic":
            raise SystemExit(
                "--grad-reduction bucketed/overlapped addresses the "
                "sequence-parallel engine's explicit reducer; the "
                "expert-parallel LM engine is GSPMD — drop the flag"
            )
        if args.moe_overlap and args.moe_dispatch != "hierarchical":
            raise SystemExit(
                "--moe-overlap chunks the hierarchical exchange; set "
                "--moe-dispatch hierarchical"
            )
        if (
            args.dcn_compression != "none"
            and args.moe_dispatch != "hierarchical"
        ):
            raise SystemExit(
                "--dcn-compression compresses the hierarchical "
                "exchange's cross-slice messages; the gspmd dispatch "
                "has no explicit 'dcn' hop — set --moe-dispatch "
                "hierarchical (with --dcn-slices >= 2) or drop the flag"
            )
        if args.moe_dispatch == "hierarchical" and args.expert_shards != 1:
            raise SystemExit(
                "--moe-dispatch hierarchical shards experts over the "
                "(factored) data fabric; --expert-shards must stay 1 "
                "(the 'expert' axis is the gspmd layout)"
            )
    check_grad_reduction_args(args)
    check_checkpoint_args(args)
    if args.pipeline_stages > 1 and (
        args.grad_reduction != "monolithic"
        or args.dcn_slices != 1
        or args.dcn_compression != "none"
    ):
        raise SystemExit(
            "--grad-reduction bucketed/overlapped / --dcn-slices / "
            "--dcn-compression address the sequence-parallel engine's "
            "data-axis gradient collective; the pipeline engine "
            "reduces over 'stage' wires — drop the flags or "
            "--pipeline-stages"
        )
    if args.grad_reduction == "overlapped":
        if args.layers < 2:
            raise SystemExit(
                "--grad-reduction overlapped splits the decoder stack "
                f"into >= 2 backward segments; --layers {args.layers} "
                "leaves nothing to overlap"
            )
        if args.overlap_stages > args.layers:
            raise SystemExit(
                f"--overlap-stages {args.overlap_stages} exceeds "
                f"--layers {args.layers}: a backward segment needs at "
                "least one decoder block"
            )
    if args.pipeline_stages > 1:
        check_pipeline_schedule_args(
            args.pipeline_schedule, args.virtual_stages,
            args.microbatches, args.pipeline_stages,
        )
    num_chunks = args.pipeline_stages * args.virtual_stages
    if args.pipeline_stages > 1 and num_chunks > args.layers:
        raise SystemExit(
            f"--pipeline-stages {args.pipeline_stages} x "
            f"--virtual-stages {args.virtual_stages} = {num_chunks} "
            f"chunks exceeds --layers {args.layers}: a chunk needs at "
            f"least one decoder block"
        )
    if plan is not None:
        # build_plan_engine lays its own stage-major plan mesh; the
        # divisibility checks mirror check_batch_divisibility for the
        # composed tick program's shapes.
        mesh = None
        n_dev = len(jax.devices())
        if plan.num_devices > n_dev:
            raise SystemExit(
                f"--plan {plan.spec} needs {plan.num_devices} "
                f"device(s), {n_dev} present"
            )
        # The engine's default M mirrors this: pp*V chunks for the
        # interleaved schedule, pp otherwise.
        plan_mb = (
            args.microbatches if args.microbatches != 1
            else plan.pp * plan.virtual_stages
        )
        if args.batch_size % max(plan.dp * plan_mb, 1):
            raise SystemExit(
                f"--batch-size {args.batch_size} must divide into "
                f"{plan_mb} microbatch(es) x {plan.dp}-way 'data' "
                f"shards (plan {plan.spec})"
            )
        if args.seq_len % plan.tp_or_sp:
            raise SystemExit(
                f"--seq-len {args.seq_len} not divisible by plan "
                f"{plan.spec}'s {plan.tp_or_sp}-way 'seq' axis"
            )
    elif args.pipeline_stages > 1:
        mesh = make_mesh(MeshSpec(data=-1, stage=args.pipeline_stages))
        check_batch_divisibility(
            args.batch_size, mesh, microbatches=args.microbatches
        )
    elif args.moe_experts > 0:
        mesh = make_mesh(MeshSpec(
            data=-1, expert=args.expert_shards, dcn=args.dcn_slices,
        ))
        check_batch_divisibility(args.batch_size, mesh)
        if args.moe_dispatch == "hierarchical":
            from distributed_model_parallel_tpu.runtime.mesh import (
                data_axis_size,
            )

            ways = data_axis_size(mesh)
            if args.moe_experts % ways:
                raise SystemExit(
                    f"--moe-dispatch hierarchical shards "
                    f"--moe-experts {args.moe_experts} over the "
                    f"{ways}-way data fabric; the count must divide "
                    "evenly (each device owns an E/S expert block)"
                )
    else:
        mesh = make_mesh(MeshSpec(
            data=-1, seq=args.seq_shards, dcn=args.dcn_slices,
        ))
        check_batch_divisibility(args.batch_size, mesh)
    if args.seq_len % args.seq_shards:
        raise SystemExit(
            f"--seq-len {args.seq_len} not divisible by --seq-shards "
            f"{args.seq_shards}"
        )
    cfg = model_cfg or GPTConfig(
        vocab_size=args.vocab_size,
        dim=args.dim,
        num_layers=args.layers,
        num_heads=args.heads,
        ffn_dim=args.ffn_dim or 4 * args.dim,
        max_position=args.seq_len,
        dropout_rate=args.dropout,
        pad_token_id=0,
        num_experts=args.moe_experts,
        moe_every=args.moe_every,
    )
    if plan is not None:
        from distributed_model_parallel_tpu.parallel.plan import (
            build_plan_engine,
        )

        try:
            engine = build_plan_engine(
                cfg, build_optimizer(args), plan,
                num_microbatches=(
                    args.microbatches if args.microbatches != 1
                    else None
                ),
                attention=args.attention,
                collective_matmul=args.collective_matmul,
                compute_dtype=compute_dtype_from_flag(args.dtype),
                remat=args.remat,
            )
        except (ValueError, NotImplementedError) as e:
            raise SystemExit(f"--plan {plan.spec}: {e}") from e
        local = getattr(engine, "local_attention", None)
        if local is not None and jax.process_index() == 0:
            print(f"plan {plan.spec}: the sequence is whole on a chip, "
                  f"local attention: {local}")
        exchange = getattr(engine, "fsdp_exchange", None)
        if exchange is not None and jax.process_index() == 0:
            print(f"plan {plan.spec}: parameters 1/{plan.dp} over 'data', "
                  f"{exchange}")
    elif args.pipeline_stages > 1:
        from distributed_model_parallel_tpu.models.gpt import split_stages
        from distributed_model_parallel_tpu.parallel.pipeline import (
            LMPipelineEngine,
        )

        engine = LMPipelineEngine(
            split_stages(num_chunks, cfg),
            build_optimizer(args),
            mesh,
            num_microbatches=args.microbatches,
            compute_dtype=compute_dtype_from_flag(args.dtype),
            remat=args.remat,
            schedule=args.pipeline_schedule,
            virtual_stages=args.virtual_stages,
            pad_token_id=cfg.pad_token_id,
        )
    elif args.moe_experts > 0:
        from distributed_model_parallel_tpu.models.gpt import gpt_lm
        from distributed_model_parallel_tpu.parallel.expert_parallel import (
            ExpertParallelLMEngine,
        )

        engine = ExpertParallelLMEngine(
            gpt_lm(cfg, remat=args.remat),
            build_optimizer(args),
            mesh,
            dispatch=args.moe_dispatch,
            overlap=args.moe_overlap,
            dcn_compression=args.dcn_compression,
            pad_token_id=cfg.pad_token_id,
            compute_dtype=compute_dtype_from_flag(args.dtype),
        )
    else:
        try:
            engine = CausalLMSequenceParallelEngine(
                cfg, build_optimizer(args), mesh,
                attention=args.attention,
                compute_dtype=compute_dtype_from_flag(args.dtype),
                remat=args.remat,
                collective_matmul=args.collective_matmul,
                grad_reduction=args.grad_reduction,
                bucket_mb=args.bucket_mb,
                overlap_stages=args.overlap_stages,
                dcn_compression=args.dcn_compression,
            )
        except NotImplementedError as e:
            raise SystemExit(str(e)) from e
    corpus = synthetic_corpus(
        args.vocab_size, args.corpus_tokens, seed=args.corpus_seed
    )
    val_corpus = synthetic_corpus(
        args.vocab_size,
        max(args.corpus_tokens // 8, args.seq_len * args.batch_size),
        seed=args.corpus_seed,              # SAME chain...
        stream_seed=args.corpus_seed + 1,   # ...different walk
    )
    train = LMLoader(corpus, args.batch_size, args.seq_len,
                     seed=args.corpus_seed)
    val = LMLoader(val_corpus, args.batch_size, args.seq_len,
                   shuffle=False, seed=args.corpus_seed)
    floor = chain_entropy(args.vocab_size, seed=args.corpus_seed)
    if jax.process_index() == 0:
        print(f"corpus loss floor (chain conditional entropy): "
              f"{floor:.4f} nats/token")
    tcfg = TrainerConfig(
        epochs=args.epochs,
        base_lr=args.lr,
        t_max=max(args.epochs - args.epochs // 10, 1),
        warmup_period=max(args.epochs // 10, 1),
        log_file=args.log_file or f"lm_{args.batch_size}.txt",
        checkpoint_dir=args.checkpoint_dir,
        resume=args.resume,
        steps_per_epoch=args.steps_per_epoch,
        steps_per_dispatch=args.steps_per_dispatch,
        profile_dir=args.profile_dir,
        checkpoint_format=args.checkpoint_format,
        async_save=args.async_save,
        # Recorded in the checkpoint sidecar/manifest so `cli/serve.py
        # --checkpoint` can fail fast, naming the exact field, when the
        # serve flags disagree with the trained architecture: the
        # family's own record (`gpt_config` for GPT, by whose
        # `num_experts` serve refuses MoE checkpoints; `lm_family` with
        # the configuration's shape for a --model-config family, which
        # serve refuses by that field).
        checkpoint_extra=cfg.lm_family().checkpoint_extra,
    )
    trainer = Trainer(engine, train, val, tcfg, rng=jax.random.PRNGKey(0))
    out = trainer.fit()
    out["loss_floor"] = floor
    from distributed_model_parallel_tpu.cli.common import (
        export_metrics_out,
    )

    export_metrics_out(args.metrics_out)
    return out


if __name__ == "__main__":
    main()
