"""Shared CLI plumbing: dataset/loader construction and model selection."""

from __future__ import annotations

import argparse
from typing import Tuple

import jax
import numpy as np

from distributed_model_parallel_tpu.data.datasets import (
    CIFAR10_MEAN,
    CIFAR10_STD,
    IMAGENET_MEAN,
    IMAGENET_STD,
    DatasetCollection,
)
from distributed_model_parallel_tpu.data.loader import Loader
from distributed_model_parallel_tpu.models import (
    mobilenet_v2,
    mobilenet_v2_nobn,
    mobilenetv2,
    resnet,
    resnet18,
    resnet50,
    tiny_cnn,
    tinycnn,
    vit_cifar,
)

def _bert_tiny_cfg():
    from distributed_model_parallel_tpu.models.bert import BertConfig

    # Sized for the SyntheticText task (vocab 512, seq 64) and fast
    # CI/smoke compiles; the full 'bert' entry uses BERT_BASE.
    return BertConfig(
        vocab_size=512, hidden_size=128, num_layers=4, num_heads=4,
        intermediate_size=256, max_position=128,
    )


def _bert_model(num_classes: int, cfg=None, *, remat: bool = False):
    from distributed_model_parallel_tpu.models.bert import (
        BERT_BASE,
        bert_for_classification,
    )

    return bert_for_classification(
        num_classes, cfg or BERT_BASE, remat=remat
    )


def _bert_stages(num_stages, num_classes, boundaries, cfg=None):
    from distributed_model_parallel_tpu.models import bert

    return bert.split_stages(
        num_stages, num_classes, cfg or bert.BERT_BASE,
        boundaries=boundaries,
    )


MODELS = {
    "mobilenetv2": mobilenet_v2,
    "mobilenetv2_nobn": mobilenet_v2_nobn,
    "resnet18": resnet18,
    "resnet50": resnet50,
    "tinycnn": tiny_cnn,
    "vit": vit_cifar,  # CIFAR-scale ViT (32^2 inputs, 4x4 patches)
    # Token-id classifiers (pair with --dataset-type SyntheticText):
    "bert": _bert_model,
    "bert_tiny": lambda c, *, remat=False: _bert_model(
        c, _bert_tiny_cfg(), remat=remat
    ),
}

# Models whose blocks route every projection through `layers.project` —
# the collective-matmul hook (`ops/collective_matmul.py`). Kept beside
# MODELS so a new transformer-family entry extends both in one place;
# --collective-matmul is rejected for models outside this set (the flag
# would silently do nothing).
TRANSFORMER_MODELS = ("bert", "bert_tiny", "vit")

# Pipeline stage builders, kept beside MODELS so both CLIs extend in one
# place: name -> fn(num_stages, num_classes, boundaries) -> [Layer].
# `num_stages` counts CHUNKS: an interleaved virtual pipeline
# (--pipeline-schedule interleaved --virtual-stages V) passes S·V here,
# and the engine deals the chunks round-robin to the S devices
# (models/staging.py `chunk_owner`).
STAGE_BUILDERS = {
    "mobilenetv2": lambda n, c, b: mobilenetv2.split_stages(
        n, c, boundaries=b
    ),
    "mobilenetv2_nobn": lambda n, c, b: mobilenetv2.split_stages(
        n, c, batchnorm=False, boundaries=b
    ),
    "resnet18": lambda n, c, b: resnet.split_stages(
        18, n, c, cifar=True, boundaries=b
    ),
    "resnet50": lambda n, c, b: resnet.split_stages(
        50, n, c, boundaries=b
    ),
    "tinycnn": lambda n, c, b: tinycnn.split_stages(n, c, boundaries=b),
    # Transformer pipelines: the wire carries the (hidden, mask) pair.
    "bert": _bert_stages,
    "bert_tiny": lambda n, c, b: _bert_stages(n, c, b, _bert_tiny_cfg()),
}


def build_optimizer(args):
    """--optimizer flag -> optimizer instance. --wd keeps its surface
    meaning for both (decay strength); --momentum applies to sgd only."""
    from distributed_model_parallel_tpu.training.optim import SGD, AdamW

    if args.optimizer == "adamw":
        return AdamW(weight_decay=args.weight_decay)
    return SGD(momentum=args.momentum, weight_decay=args.weight_decay)


def build_model(name: str, num_classes: int, *, remat: bool = False):
    if name not in MODELS:
        raise SystemExit(f"unknown model {name!r}; choose from {sorted(MODELS)}")
    return MODELS[name](num_classes, remat=remat)


def stats_for(dataset_type: str) -> Tuple[np.ndarray, np.ndarray]:
    if dataset_type in ("CIFAR10", "Synthetic", "SyntheticTextures"):
        return CIFAR10_MEAN, CIFAR10_STD
    return IMAGENET_MEAN, IMAGENET_STD


def build_loaders(
    dataset_type: str,
    data_path: str,
    batch_size: int,
    *,
    val_batch_size: int | None = None,
    augment: bool = True,
    seed: int = 0,
    workers: int = 1,
    device_normalize: bool = False,
    compose_train=None,
    compose_val=None,
):
    """(train_loader, val_loader, num_classes) with per-host sharding —
    the DistributedSampler the reference lacks (`utils.py:21`).

    `batch_size` / `val_batch_size` are GLOBAL batch sizes (the reference's
    `-b 512` means 512 total, and lr=0.4 is tuned to that); each host's
    Loader draws global/process_count samples per step."""
    procs = _check_process_divisibility(batch_size, val_batch_size)
    if device_normalize and (compose_train or compose_val):
        raise SystemExit(
            "--device-normalize conflicts with caller-supplied compose "
            "transforms: the compose replaces the host normalize, and "
            "the engine would normalize its output AGAIN on device"
        )
    collection = DatasetCollection(
        dataset_type, data_path, compose_train, compose_val
    )
    train_ds, val_ds = collection.init()
    mean, std = stats_for(dataset_type)
    text = getattr(train_ds, "kind", "image") == "text"
    if text:
        # Token-id batches: no crop/flip, no /255-mean/std — raw wire.
        mean = std = None
        augment = False
    train = Loader(
        train_ds,
        batch_size=batch_size // procs,
        shuffle=True,
        augment=augment,
        mean=mean,
        std=std,
        seed=seed,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        workers=workers,
        device_normalize=device_normalize,
        raw=text,
        # The collection is the single source of truth for the composes
        # (the reference's constructor surface); read them back from it.
        transform=collection.compose_train,
    )
    val = Loader(
        val_ds,
        batch_size=(val_batch_size or batch_size) // procs,
        shuffle=False,
        augment=False,
        mean=mean,
        std=std,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        drop_last=False,
        workers=workers,
        device_normalize=device_normalize,
        raw=text,
        transform=collection.compose_val,
    )
    return train, val, train_ds.num_classes


def _check_process_divisibility(
    batch_size: int, val_batch_size: int | None
) -> int:
    """Shared by `build_loaders` / `build_index_loaders`: global batches
    must divide across hosts. Returns the process count."""
    procs = jax.process_count()
    if batch_size % procs:
        raise SystemExit(
            f"global batch size {batch_size} must be divisible by the "
            f"process count {procs}"
        )
    if val_batch_size is not None and val_batch_size % procs:
        raise SystemExit(
            f"global val batch size {val_batch_size} must be divisible by "
            f"the process count {procs}"
        )
    return procs


def build_index_loaders(
    dataset_type: str,
    data_path: str,
    batch_size: int,
    mesh,
    *,
    val_batch_size: int | None = None,
    augment: bool = True,
    seed: int = 0,
):
    """The `--device-cache` twin of `build_loaders`: same per-host batch
    division and dataset construction, but the loaders yield INDEX
    vectors and the whole dataset uploads to HBM once (`combined_cache`).
    Returns (train_loader, val_loader, num_classes, input_transform)."""
    from distributed_model_parallel_tpu.data.device_cache import (
        IndexLoader,
        combined_cache,
    )

    procs = _check_process_divisibility(batch_size, val_batch_size)
    train_ds, val_ds = DatasetCollection(dataset_type, data_path).init()
    mean, std = stats_for(dataset_type)
    transform, val_off = combined_cache(
        train_ds, val_ds, mesh, augment=augment, mean=mean, std=std,
    )
    train = IndexLoader(
        train_ds, batch_size=batch_size // procs, shuffle=True, seed=seed,
        process_index=jax.process_index(), process_count=procs,
    )
    val = IndexLoader(
        val_ds, batch_size=(val_batch_size or batch_size) // procs,
        shuffle=False, drop_last=False, index_offset=val_off,
        process_index=jax.process_index(), process_count=procs,
    )
    return train, val, train_ds.num_classes, transform


def check_batch_divisibility(
    global_batch: int, mesh, *, microbatches: int = 1, label: str = "batch"
) -> None:
    """Fail at startup (not at trace time, possibly an epoch in) when the
    batch cannot be laid out on the mesh: the global batch shards over the
    data axes (the 'data' axis, or 'dcn'×'ici' on a hybrid mesh), and
    each device's shard must split into `microbatches` equal microbatches
    for the pipeline schedule."""
    from distributed_model_parallel_tpu.runtime.mesh import (
        data_axis_names,
        data_axis_size,
    )

    axes = "x".join(f"'{a}'" for a in data_axis_names(mesh))
    data_axis = data_axis_size(mesh)
    if global_batch % data_axis:
        raise SystemExit(
            f"{label} size {global_batch} must be divisible by the "
            f"{axes} mesh axes ({data_axis} shards)"
        )
    local = global_batch // data_axis
    if local % microbatches:
        raise SystemExit(
            f"{label} size {global_batch} gives {local} samples per 'data' "
            f"shard, not divisible by --microbatches {microbatches}"
        )


def check_pipeline_schedule_args(
    schedule: str, virtual_stages: int, microbatches: int, num_stages: int
) -> None:
    """Startup-time validation of the (schedule, V, M, S) surface shared
    by both pipeline CLIs — fail before loaders/meshes are built, with
    CLI-flag vocabulary, instead of at engine construction:

    * --virtual-stages is an interleaved-only knob (gpipe/1f1b run one
      chunk per device; a silent no-op flag would mislabel the run);
    * interleaving needs >= 2 physical stages (one device has no bubble
      to divide);
    * V > 1 needs --microbatches divisible by the stage count
      (Megatron's round-robin microbatch groups — the schedule builder
      enforces the same)."""
    if virtual_stages < 1:
        raise SystemExit(
            f"--virtual-stages must be >= 1, got {virtual_stages}"
        )
    if virtual_stages > 1 and schedule != "interleaved":
        raise SystemExit(
            "--virtual-stages > 1 requires --pipeline-schedule "
            "interleaved (gpipe/1f1b run exactly one model chunk per "
            "device, so the flag would silently do nothing)"
        )
    if schedule == "interleaved":
        if num_stages < 2:
            raise SystemExit(
                "--pipeline-schedule interleaved needs >= 2 pipeline "
                "stages (a one-device pipeline has no bubble to divide)"
            )
        if virtual_stages > 1 and microbatches % num_stages:
            raise SystemExit(
                f"interleaved schedule needs --microbatches divisible "
                f"by the stage count (got M={microbatches}, "
                f"S={num_stages}) — Megatron's round-robin microbatch "
                f"groups"
            )


def add_grad_reduction_flags(parser: argparse.ArgumentParser) -> None:
    """The bucketed-reducer surface shared by the data_parallel and lm
    CLIs (`ops/grad_reduction.py`)."""
    parser.add_argument(
        "--grad-reduction", default="monolithic",
        choices=("monolithic", "bucketed", "overlapped"),
        help="gradient reduction lowering: monolithic = one fused "
             "all-reduce of the whole grad pytree (the GSPMD default); "
             "bucketed = DDP-Reducer-style ~--bucket-mb flat buckets in "
             "reverse parameter order, each a chunked ppermute "
             "reduce-scatter/all-gather ring that interleaves with the "
             "remaining backward — hierarchical over a --dcn-slices "
             "factored mesh (same math); overlapped = the bucketed "
             "rings fired EAGERLY from a stagewise backward (the model "
             "is cut into --overlap-stages segments, late layers "
             "differentiate first and their buckets launch while "
             "earlier segments are still running — the DDP Reducer's "
             "autograd-hook overlap; same math)",
    )
    # None sentinel = "flag not passed": check_grad_reduction_args can
    # then reject an explicit --bucket-mb without bucketed mode (any
    # value, including 25) and resolves the default itself — one place
    # owns the number.
    parser.add_argument(
        "--bucket-mb", default=None, type=float,
        help="flat-buffer bucket size in MB under --grad-reduction "
             "bucketed (the Reducer's bucket_cap_mb; default 25)",
    )
    parser.add_argument(
        "--dcn-slices", default=1, type=int,
        help="cross-slice (DCN) factor of the data axis: the mesh "
             "carries ('dcn', 'ici') in place of 'data' so collectives "
             "can address the two fabrics separately (bucketed "
             "reduction then reduce-scatters over the intra-slice ring "
             "and all-reduces only the 1/N shard across slices). On a "
             "single process this is a virtual split",
    )
    # None sentinel, like --bucket-mb: reject the flag outside
    # --grad-reduction overlapped, resolve the auto default (0 = the
    # engine's min(4, n_blocks)) otherwise.
    parser.add_argument(
        "--overlap-stages", default=None, type=int,
        help="backward segment count under --grad-reduction overlapped: "
             "the model's blocks are cut into this many vjp segments "
             "(pipeline-style split points) and each segment's buckets "
             "fire as soon as its backward completes (default: "
             "min(4, model blocks))",
    )
    parser.add_argument(
        "--dcn-compression", default="none",
        choices=("none", "bf16", "int8"),
        help="compress the cross-slice 'dcn' hop of every explicit "
             "exchange — the bucket reduction's per-slice shard "
             "messages and (on the lm CLI) the hierarchical MoE "
             "dispatch's regrouped messages — to this wire dtype "
             "(ops/wire_codec.py: bf16 = cast codec, 1/2 the dcn "
             "bytes; int8 = absmax-scale codec + f32 scale sidecar, "
             "1/4 the bytes; int8 never sums in int8 — chunks decode "
             "before accumulating). Master weights, intra-slice rings "
             "and all math stay full precision; requires --dcn-slices "
             ">= 2 (the compressed hop IS the slice boundary)",
    )


def check_grad_reduction_args(args) -> None:
    """Startup-time validation of the shared reducer flags: fail with
    CLI vocabulary before datasets/meshes are built. Resolves the
    `--bucket-mb` None sentinel to the 25 MB default afterward."""
    if args.bucket_mb is not None:
        if args.bucket_mb <= 0:
            raise SystemExit(
                f"--bucket-mb must be > 0, got {args.bucket_mb}"
            )
        if args.grad_reduction not in ("bucketed", "overlapped"):
            raise SystemExit(
                "--bucket-mb sizes the bucketed reducer's flat "
                "buffers; it only applies under --grad-reduction "
                "bucketed / overlapped"
            )
    else:
        args.bucket_mb = 25.0
    if args.overlap_stages is not None:
        if args.grad_reduction != "overlapped":
            raise SystemExit(
                "--overlap-stages cuts the stagewise backward; it only "
                "applies under --grad-reduction overlapped"
            )
        if args.overlap_stages < 2:
            raise SystemExit(
                "--overlap-stages must be >= 2 (one segment is the "
                f"monolithic backward), got {args.overlap_stages}"
            )
    else:
        args.overlap_stages = 0  # engine auto: min(4, model blocks)
    if args.dcn_slices < 1:
        raise SystemExit(
            f"--dcn-slices must be >= 1, got {args.dcn_slices}"
        )
    if args.dcn_compression != "none" and args.dcn_slices < 2:
        raise SystemExit(
            "--dcn-compression compresses the cross-slice 'dcn' hop, "
            "and this run has no 'dcn' axis to cross — factor the data "
            "axis with --dcn-slices >= 2 (or drop --dcn-compression)"
        )


def check_overlapped_model(name: str, overlap_stages: int = 0) -> None:
    """Fail fast (before datasets/meshes are built) when
    `--grad-reduction overlapped` is pointed at a model that cannot be
    cut into >= 2 backward segments, or `--overlap-stages` asks for more
    segments than the model has blocks — the stagewise engines would
    raise the same complaints, but only after the data pipeline was paid
    for. Builds the model STRUCTURE only (no init, no arrays)."""
    if name not in MODELS:
        return  # build_model raises the canonical unknown-model error
    probe = MODELS[name](10)
    parts = getattr(probe, "parts", None)
    n_blocks = len(parts.blocks) if parts is not None else 0
    if n_blocks < 2:
        raise SystemExit(
            "--grad-reduction overlapped splits the backward into >= 2 "
            f"segments; --model {name} exposes {n_blocks} block(s) "
            "(models/staging.staged_model anatomy)"
        )
    if overlap_stages > n_blocks:
        raise SystemExit(
            f"--overlap-stages {overlap_stages} exceeds the "
            f"{n_blocks} blocks --model {name} exposes; each backward "
            "segment needs at least one block"
        )


def add_checkpoint_flags(parser: argparse.ArgumentParser) -> None:
    """The checkpoint-format surface shared by the training CLIs
    (`checkpointing/`): sharded parallel saves, async off-step-path
    writes, resharding restore."""
    parser.add_argument(
        "--checkpoint-dir", default="./checkpoint",
        help="checkpoint directory (reference: ./checkpoint)",
    )
    parser.add_argument(
        "--checkpoint-format", default="legacy",
        choices=("legacy", "sharded"),
        help="legacy = one .npz gathered to host 0 (the reference's "
             "shape); sharded = each process writes only its "
             "locally-addressable shards + a JSON manifest "
             "(ZeRO-style parallel save — no cross-process gather on "
             "the save path; restore reshards onto the current mesh, "
             "so an elastic restart may resize). Restore auto-detects "
             "either format",
    )
    parser.add_argument(
        "--async-save", action="store_true",
        help="move checkpoint file I/O off the step path (sharded "
             "format only): one device->host snapshot, then a "
             "background writer thread; write errors surface at the "
             "next save or at fit() exit, never silently",
    )


def check_checkpoint_args(args) -> None:
    """Startup-time validation of the shared checkpoint flags (the
    Trainer enforces the same, but only after datasets/meshes are
    built)."""
    if args.async_save and args.checkpoint_format != "sharded":
        raise SystemExit(
            "--async-save moves the sharded writer off the step path; "
            "it requires --checkpoint-format sharded (the legacy "
            "format gathers to host 0 synchronously by design)"
        )


def check_serving_args(args) -> None:
    """Startup-time validation of the serving CLI surface
    (`cli/serve.py`), mirroring the other `check_*_args` guards: fail
    with CLI vocabulary before meshes/engines are built, and reject
    training-side flags that would silently do nothing on an
    inference-only run.

    The serve parser deliberately CARRIES the shared training flags
    (`add_grad_reduction_flags`, --pipeline-stages) so a launch line
    pasted from the lm CLI fails with an explanation here instead of an
    opaque argparse error."""
    if args.pipeline_stages != 1:
        raise SystemExit(
            "--pipeline-stages selects a TRAINING engine's stage wires; "
            "serving decodes token-by-token through one replica's "
            "layers (compose tp/sp layouts instead) — drop the flag"
        )
    if args.grad_reduction != "monolithic":
        raise SystemExit(
            "--grad-reduction configures the training engines' gradient "
            "collective; serving runs no backward — drop the flag"
        )
    if args.bucket_mb is not None:
        raise SystemExit(
            "--bucket-mb sizes gradient-reduction buckets; serving runs "
            "no backward — drop the flag"
        )
    if args.overlap_stages is not None:
        raise SystemExit(
            "--overlap-stages cuts the stagewise backward; serving runs "
            "no backward — drop the flag"
        )
    if args.dcn_slices != 1:
        raise SystemExit(
            "--dcn-slices factors the data axis for gradient traffic; "
            "the serving meshes are 'model'/'seq' only — drop the flag"
        )
    if args.dcn_compression != "none":
        raise SystemExit(
            "--dcn-compression compresses the training engines' "
            "cross-slice gradient/dispatch hop; the serving meshes "
            "have no 'dcn' fabric — drop the flag"
        )
    if args.layout == "tp":
        if args.model_shards < 2:
            raise SystemExit(
                "--layout tp shards heads over the 'model' axis; "
                "--model-shards must be >= 2 (1 shard IS the "
                "replicated layout — use --layout replicated)"
            )
        if args.seq_shards != 1:
            raise SystemExit(
                "--seq-shards belongs to --layout sp; the tp layout "
                "rings over 'model' — drop one of the flags"
            )
    elif args.layout == "sp":
        if args.seq_shards < 2:
            raise SystemExit(
                "--layout sp shards cache positions over the 'seq' "
                "axis; --seq-shards must be >= 2 (1 shard IS the "
                "replicated layout — use --layout replicated)"
            )
        if args.model_shards != 1:
            raise SystemExit(
                "--model-shards belongs to --layout tp; the sp layout "
                "shards over 'seq' — drop one of the flags"
            )
    else:  # replicated
        if args.model_shards != 1 or args.seq_shards != 1:
            raise SystemExit(
                "--model-shards / --seq-shards select the tp / sp "
                "layouts; pass --layout tp or --layout sp explicitly"
            )
    if args.collective_matmul and args.layout != "tp":
        raise SystemExit(
            "--collective-matmul rings decode projections over the "
            "'model' axis; it requires --layout tp with "
            "--model-shards >= 2"
        )
    if getattr(args, "compute_dtype", "f32") != "f32":
        if args.dtype != "float32":
            raise SystemExit(
                "--dtype and --compute-dtype both set the decode "
                "arithmetic; --dtype bfloat16 is the legacy spelling "
                "of --compute-dtype bf16 — pass only --compute-dtype"
            )
        if args.compute_dtype == "int8" and args.layout == "sp":
            raise SystemExit(
                "--compute-dtype int8 quantizes the decode projection "
                "GEMMs (replicated/tp layouts); the sp layout's "
                "shard_map decode has no quantized policy path — use "
                "bf16 or a tp/replicated layout"
            )
    # --- paged-cache knobs (serving/kv_cache.py) ---------------------
    if args.page_size < 0:
        raise SystemExit(
            f"--page-size must be >= 0, got {args.page_size}"
        )
    if args.page_size:
        if args.max_len % args.page_size:
            raise SystemExit(
                f"--page-size {args.page_size} must divide --max-len "
                f"{args.max_len} (the block table covers whole pages)"
            )
        if args.layout == "sp" and args.page_size % args.seq_shards:
            raise SystemExit(
                f"--layout sp shards each page's positions over "
                f"'seq': --page-size {args.page_size} must be "
                f"divisible by --seq-shards {args.seq_shards}"
            )
    else:
        for val, flag in ((args.kv_pages, "--kv-pages"),
                          (args.prefill_chunk, "--prefill-chunk")):
            if val:
                raise SystemExit(
                    f"{flag} configures the block-paged KV cache; set "
                    "--page-size as well (0 = contiguous slots)"
                )
        if args.prefix_cache:
            raise SystemExit(
                "--prefix-cache shares pool PAGES between slots; it "
                "requires --page-size (the contiguous layout has no "
                "sharable unit)"
            )
    if args.kv_pages < 0:
        raise SystemExit(
            f"--kv-pages must be >= 0, got {args.kv_pages}"
        )
    if args.prefill_chunk < 0:
        raise SystemExit(
            f"--prefill-chunk must be >= 0, got {args.prefill_chunk}"
        )
    if args.prefill_chunk and args.layout == "sp":
        raise SystemExit(
            "--prefill-chunk is not supported under --layout sp: sp "
            "prefill rides the training ring over 'seq' in one pass — "
            "drop the flag or use the replicated/tp layouts"
        )
    if args.prefix_cache:
        if args.layout == "sp":
            raise SystemExit(
                "--prefix-cache is not supported under --layout sp "
                "(shared pages would need coherent copy-on-write "
                "across 'seq' shards)"
            )
        if not args.prefill_chunk:
            raise SystemExit(
                "--prefix-cache needs --prefill-chunk: a partial "
                "prefix hit resumes ingestion mid-prompt, which only "
                "the chunked path can do"
            )
    # --- sampling knobs (serving/sampling.py) ------------------------
    if args.temperature < 0:
        raise SystemExit(
            f"--temperature must be >= 0, got {args.temperature}"
        )
    if args.top_k < 0:
        raise SystemExit(f"--top-k must be >= 0, got {args.top_k}")
    if not 0 < args.top_p <= 1:
        raise SystemExit(
            f"--top-p must be in (0, 1], got {args.top_p}"
        )
    if args.temperature == 0 and (args.top_k or args.top_p < 1):
        raise SystemExit(
            "--top-k/--top-p filter a SAMPLING distribution; with the "
            "greedy default (--temperature 0) they would silently do "
            "nothing — set --temperature > 0"
        )
    # --- speculative decoding (serving/speculative.py) ---------------
    spec_k = getattr(args, "speculative_k", 0)
    if spec_k < 0 or spec_k > 8:
        raise SystemExit(
            f"--speculative-k must be in [0, 8] (0 = off; past ~8 the "
            f"verify step's wasted work dominates), got {spec_k}"
        )
    if spec_k:
        if args.layout == "sp":
            raise SystemExit(
                "--speculative-k is not supported under --layout sp: "
                "the verify step rides the chunk-shaped paged decode "
                "path, which sp's shard_map decode does not lower — "
                "use the replicated/tp layouts"
            )
        if not args.page_size:
            raise SystemExit(
                "--speculative-k rolls rejected draft suffixes back by "
                "TRUNCATING THE BLOCK TABLE; it requires --page-size "
                "(the contiguous layout has no page-granular rollback)"
            )
        if spec_k + 1 >= args.max_len:
            raise SystemExit(
                f"--speculative-k {spec_k} writes k+1 positions per "
                f"verify round; --max-len {args.max_len} cannot hold "
                "one round past the prompt"
            )
        draft_layers = getattr(args, "speculative_draft_layers", 0)
        if draft_layers < 0:
            raise SystemExit(
                f"--speculative-draft-layers must be >= 0 (0 = "
                f"max(1, --layers // 2)), got {draft_layers}"
            )
        if getattr(args, "speculative_draft", None) and draft_layers:
            raise SystemExit(
                "--speculative-draft-layers sizes a FRESH-INIT draft; "
                "--speculative-draft supplies the draft's dims from "
                "its recorded config — drop one of the flags"
            )
    else:
        for val, flag in (
            (getattr(args, "speculative_draft", None),
             "--speculative-draft"),
            (getattr(args, "speculative_draft_layers", 0),
             "--speculative-draft-layers"),
        ):
            if val:
                raise SystemExit(
                    f"{flag} configures the draft model for "
                    "speculative decoding; set --speculative-k >= 1 "
                    "as well (0 = off)"
                )
    # --- synthetic arrivals (Poisson offered load) -------------------
    rate = getattr(args, "arrival_rate", 0.0)
    burst = getattr(args, "arrival_burst", 1)
    if rate < 0:
        raise SystemExit(
            f"--arrival-rate must be >= 0 (0 = all requests arrive "
            f"at t=0), got {rate}"
        )
    if burst < 1:
        raise SystemExit(
            f"--arrival-burst must be >= 1, got {burst}"
        )
    if burst > 1 and not rate:
        raise SystemExit(
            "--arrival-burst groups Poisson arrival events into "
            "bursts; set --arrival-rate > 0 as well"
        )


def compute_dtype_from_flag(name: str):
    """--dtype flag value -> engine compute_dtype (None = pure f32)."""
    import jax.numpy as jnp

    return {"float32": None, "bfloat16": jnp.bfloat16}[name]


def serve_compute_dtype(args):
    """--compute-dtype (preferred) / legacy --dtype -> ServingEngine
    compute_dtype. `check_serving_args` has already rejected setting
    both; the string triple passes through verbatim (the engine
    normalizes via `ops/quant_matmul.normalize_compute_dtype`)."""
    mode = getattr(args, "compute_dtype", "f32")
    if mode != "f32":
        return mode
    return compute_dtype_from_flag(args.dtype)


def add_common_tpu_flags(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--model", default="mobilenetv2", choices=sorted(MODELS),
        help="model family (reference hard-codes MobileNetV2)",
    )
    parser.add_argument(
        "--dtype", default="float32", choices=("float32", "bfloat16"),
        help="activation/compute dtype (params stay f32); bfloat16 is the "
             "TPU MXU's native matmul precision",
    )
    parser.add_argument(
        "--remat", action="store_true",
        help="rematerialize activations during backward (jax.checkpoint) "
             "— trades compute for HBM on deep models",
    )
    parser.add_argument(
        "--optimizer", default="sgd", choices=("sgd", "adamw"),
        help="sgd = the reference's SGD(momentum, wd) surface; adamw = "
             "decoupled-decay AdamW (the transformer-family convention)",
    )
    parser.add_argument(
        "--profile-dir", default=None,
        help="capture a jax.profiler trace of a few steady-state steps "
             "into this directory",
    )
    parser.add_argument(
        "--steps-per-epoch", default=0, type=int,
        help="truncate each epoch to N batches (0 = full epoch); "
             "for smoke runs and benchmarking",
    )
    parser.add_argument(
        "--steps-per-dispatch", default=1, type=int,
        help="fold N optimizer steps into one compiled dispatch "
             "(lax.scan; trajectory-identical to per-step). Amortizes "
             "host->device round-trips",
    )
    parser.add_argument(
        "--log-file", default=None,
        help="epoch log filename under ./log (reference: 512.txt)",
    )
    add_metrics_out_flag(parser)


def add_metrics_out_flag(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "--metrics-out", default=None, metavar="PATH",
        help="enable the metrics registry (observability/metrics.py) "
             "and write its export here at exit: Prometheus text when "
             "PATH ends in .prom, JSON otherwise (what tools/obsreport "
             "--metrics ingests). Fails fast if PATH's directory does "
             "not exist.",
    )


def setup_metrics_out(path) -> None:
    """Validate + enable for `--metrics-out` (call BEFORE anything
    compiles: a mistyped directory must not surface as a lost export
    after the whole run — same contract as serve's --trace-out)."""
    if not path:
        return
    import os

    out_dir = os.path.dirname(os.path.abspath(path))
    if not os.path.isdir(out_dir):
        raise SystemExit(
            f"--metrics-out {path}: directory {out_dir} does not exist"
        )
    from distributed_model_parallel_tpu.observability import metrics

    metrics.enable()


def export_metrics_out(path) -> None:
    """Write the registry export at run end (host 0 only)."""
    if not path or jax.process_index() != 0:
        return
    from distributed_model_parallel_tpu.observability.metrics import (
        get_metrics,
    )

    get_metrics().export(path)
    print(f"==> wrote metrics to {path}", flush=True)
