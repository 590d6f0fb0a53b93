"""Offline serving driver — the inference-side fourth launcher.

Feeds a synthetic request trace (random prompts, optionally staggered
Poisson arrivals collapsed to submission order — this sandbox has no
live traffic) through `serving.ServingEngine` under continuous
batching, and reports per-request latencies plus the aggregate
tokens/sec and p50/p99 per-token legs, as JSON on stdout.

  python -m distributed_model_parallel_tpu.cli.serve \
      --dim 128 --layers 4 --heads 4 --num-requests 32 \
      --num-slots 8 --max-len 256 --prefill-len 64
  python -m distributed_model_parallel_tpu.cli.serve \
      --layout tp --model-shards 4 --collective-matmul
  python -m distributed_model_parallel_tpu.cli.serve \
      --layout sp --seq-shards 4 --max-len 512
  python -m distributed_model_parallel_tpu.cli.serve \
      --model-config benchmark/configs/jamba2-3b.json \
      --compute-dtype bf16 --num-slots 32 --max-len 8192 \
      --page-size 64 --prefill-chunk 512 \
      --prompt-len-min 256 --prompt-len-max 4096 --max-new-tokens 64

`--model-config FILE` serves another family than GPT: FILE carries the
release's own keys (its `config.json`; `model_type` picks the family,
`torch_dtype` the dtype the weights rest in) and takes the place of the
GPT-shaping flags, which are refused beside it. Weights are random from
`--seed`. A family that keeps a recurrent state per slot
(`model_type: jamba`, `qwen3_next`) is served from the page pool with
chunked prefill only (`--page-size` and `--prefill-chunk` are
required) and refuses `--prefix-cache`, `--speculative-k`, `--layout
tp|sp` by name, each with the mechanism that is missing.

The parser carries the shared training flags (grad reduction, pipeline
stages) so a pasted training launch line fails fast with an explanation
(`cli/common.check_serving_args`) instead of silently doing nothing.
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from distributed_model_parallel_tpu.cli.common import (
    add_grad_reduction_flags,
    check_serving_args,
    serve_compute_dtype,
)
from distributed_model_parallel_tpu.models.gpt import GPTConfig
from distributed_model_parallel_tpu.runtime.dist import initialize_backend
from distributed_model_parallel_tpu.runtime.mesh import MeshSpec, make_mesh
from distributed_model_parallel_tpu.runtime.platform import (
    enable_compile_cache,
)
from distributed_model_parallel_tpu.serving.engine import ServingEngine
from distributed_model_parallel_tpu.serving.scheduler import Request


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="offline autoregressive serving (continuous "
                    "batching over a slot-paged KV cache)"
    )
    # Model (matches the lm CLI's surface; params init fresh unless
    # --checkpoint points at a trained state).
    p.add_argument("--checkpoint", default=None, metavar="DIR",
                   help="serve a TRAINED checkpoint: load the params "
                        "subtree of the newest snapshot in DIR (legacy "
                        ".npz or sharded manifest, auto-detected) "
                        "through the canonical form into the selected "
                        "layout; fails fast naming the mismatch when "
                        "the checkpoint's recorded model config "
                        "disagrees with the serve flags")
    p.add_argument("--model-config", default=None, metavar="FILE",
                   help="serve the model FILE describes instead of a "
                        "GPT: a JSON object of the release's own keys "
                        "(model_type jamba: models/jamba.py). Replaces "
                        "--vocab-size/--dim/--layers/--heads/--ffn-dim, "
                        "which are refused beside it; random weights "
                        "from --seed (no --checkpoint loader for such a "
                        "family yet). What the family cannot run is "
                        "refused by name: see the module docstring")
    p.add_argument("--vocab-size", default=256, type=int)
    p.add_argument("--dim", default=128, type=int)
    p.add_argument("--layers", default=4, type=int)
    p.add_argument("--heads", default=4, type=int)
    p.add_argument("--ffn-dim", default=None, type=int,
                   help="default 4*dim")
    p.add_argument("--dtype", default="float32",
                   choices=("float32", "bfloat16"),
                   help="legacy activation-dtype spelling; superseded "
                        "by --compute-dtype (bfloat16 == bf16)")
    p.add_argument("--compute-dtype", default="f32",
                   choices=("f32", "bf16", "int8"),
                   help="decode projection GEMM arithmetic "
                        "(ops/quant_matmul.py): bf16 runs the MXU's "
                        "native half path (activations + KV cache go "
                        "bf16); int8 quantizes each decode projection "
                        "with per-output-channel weight scales and "
                        "per-token activation scales, accumulating in "
                        "int32 and dequantizing on exit (activations "
                        "and cache stay f32). Prefill always runs f32")
    # Serving surface.
    p.add_argument("--layout", default="replicated",
                   choices=("replicated", "tp", "sp"),
                   help="cache/param layout: replicated; tp = heads "
                        "over 'model' (MEGATRON_RULES params); sp = "
                        "cache positions over 'seq' (online-softmax "
                        "decode, ring-attention prefill)")
    p.add_argument("--model-shards", default=1, type=int,
                   help="'model' mesh axis size (--layout tp)")
    p.add_argument("--seq-shards", default=1, type=int,
                   help="'seq' mesh axis size (--layout sp)")
    p.add_argument("--collective-matmul", action="store_true",
                   help="latency-hiding decode rings (tp layout): "
                        "opted-in projections run as chunked ppermute "
                        "rings over the slot batch — exactly "
                        "4*layers*(S-1) permutes per decode step, no "
                        "monolithic all-gather (hlolint "
                        "serve-decode-ring)")
    p.add_argument("--num-slots", default=8, type=int,
                   help="KV-cache slots = max concurrent sequences")
    p.add_argument("--max-len", default=256, type=int,
                   help="cache positions per slot (prompt + generated)")
    p.add_argument("--prefill-len", default=64, type=int,
                   help="padded prompt length (one prefill compile)")
    # Block paging (PagedAttention; serving/kv_cache.py).
    p.add_argument("--page-size", default=0, type=int,
                   help="block-paged KV cache: pool pages of this many "
                        "positions reached through a per-slot block "
                        "table — allocation scales with live tokens, "
                        "not slots*max_len; must divide --max-len "
                        "(0 = the contiguous slot layout)")
    p.add_argument("--kv-pages", default=0, type=int,
                   help="page-pool size in pages (needs --page-size; "
                        "0 = num_slots * max_len/page_size, the "
                        "no-risk worst case — smaller pools are the "
                        "memory win, bounded by live tokens)")
    p.add_argument("--prefill-chunk", default=0, type=int,
                   help="chunked prefill: ingest prompts this many "
                        "tokens per engine iteration, interleaved with "
                        "in-flight decode so a long prompt never "
                        "stalls the batch (needs --page-size; also "
                        "lifts the --prefill-len prompt cap; 0 = "
                        "monolithic prefill)")
    p.add_argument("--prefix-cache", action="store_true",
                   help="prompt caching: share immutable prefix pages "
                        "between slots keyed on token prefix — a "
                        "repeated system prompt skips its prefill; "
                        "copy-on-write on the first divergent write "
                        "(needs --page-size and --prefill-chunk)")
    # Speculative decoding (serving/speculative.py): a small draft GPT
    # proposes k tokens per slot, the target scores all k+1 in ONE
    # verify step; acceptance is lossless (greedy output bit-identical
    # to plain decode) so these knobs are pure latency tuning.
    p.add_argument("--speculative-k", default=0, type=int,
                   help="draft tokens proposed per verify round "
                        "(0 = off; needs --page-size — rejected "
                        "suffixes roll back by truncating the block "
                        "table). Works with --prefix-cache: prefix "
                        "pages are a TARGET-side shortcut, the draft "
                        "always ingests prompts itself")
    p.add_argument("--speculative-draft", default=None, metavar="DIR",
                   help="draft model checkpoint: newest snapshot in "
                        "DIR, dims taken from its recorded config "
                        "(vocab must match the target's, recorded "
                        "max_position must cover --max-len). Omit for "
                        "a fresh-init draft sized by "
                        "--speculative-draft-layers")
    p.add_argument("--speculative-draft-layers", default=0, type=int,
                   help="layer count of the fresh-init draft when no "
                        "--speculative-draft checkpoint is given "
                        "(0 = max(1, --layers // 2); other dims "
                        "mirror the target)")
    # Synthetic arrivals: offered load instead of all-at-t=0. The
    # engine still consumes requests in submission order (this sandbox
    # has no live clock), so arrival times feed the offered-load vs
    # goodput report line, not the admission loop.
    p.add_argument("--arrival-rate", default=0.0, type=float,
                   help="Poisson arrival-EVENT rate in events/s for "
                        "the synthetic trace (0 = every request "
                        "arrives at t=0)")
    p.add_argument("--arrival-burst", default=1, type=int,
                   help="requests arriving per Poisson event (bursty "
                        "traffic: same offered load, lumpier queue; "
                        "needs --arrival-rate > 0)")
    # Decode-time sampling (serving/sampling.py; greedy default is
    # bit-stable — temperature 0 never touches an RNG).
    p.add_argument("--temperature", default=0.0, type=float,
                   help="sampling temperature (0 = greedy argmax, the "
                        "bit-stable default)")
    p.add_argument("--top-k", default=0, type=int,
                   help="keep only the k most probable tokens before "
                        "sampling (0 = no cut; needs --temperature "
                        "> 0)")
    p.add_argument("--top-p", default=1.0, type=float,
                   help="nucleus sampling: keep the smallest prefix of "
                        "probability mass reaching p (1 = no cut; "
                        "needs --temperature > 0)")
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="dump a Chrome trace_event JSON of the run "
                        "(per-request admission/prefill/decode spans, "
                        "per-step batch-occupancy counters — "
                        "observability/trace.py; open in "
                        "chrome://tracing or Perfetto). Fails fast if "
                        "PATH's directory does not exist.")
    from distributed_model_parallel_tpu.cli.common import (
        add_metrics_out_flag,
    )

    add_metrics_out_flag(p)
    # Synthetic trace.
    p.add_argument("--num-requests", default=16, type=int)
    p.add_argument("--prompt-len-min", default=4, type=int)
    p.add_argument("--prompt-len-max", default=32, type=int)
    p.add_argument("--max-new-tokens", default=32, type=int)
    p.add_argument("--seed", default=0, type=int)
    # Shared training flags, carried so pasted launch lines fail fast
    # with an explanation (check_serving_args) instead of an argparse
    # unknown-flag error.
    p.add_argument("--pipeline-stages", default=1, type=int,
                   help="TRAINING flag; rejected here (serving has no "
                        "stage wires)")
    add_grad_reduction_flags(p)
    return p


# `model_type` of a --model-config file -> its configuration's builder.
def _model_families() -> dict:
    from distributed_model_parallel_tpu.models import (
        glm_moe,
        jamba,
        qwen3_next,
    )

    return {
        jamba.MODEL_TYPE: jamba.config_from_dict,
        glm_moe.MODEL_TYPE: glm_moe.config_from_dict,
        qwen3_next.MODEL_TYPE: qwen3_next.config_from_dict,
    }


# What --model-config replaces or cannot be combined with, by flag.
_MODEL_CONFIG_SHAPE_FLAGS = (
    ("--vocab-size", "vocab_size"), ("--dim", "dim"),
    ("--layers", "layers"), ("--heads", "heads"),
    ("--ffn-dim", "ffn_dim"),
)


def _model_config(args, parser):
    """The configuration `--model-config FILE` names, after the guards
    `cli.lm --model-config` has: the file carries the shape, so the GPT
    shape flags beside it are refused by name, and so are the
    checkpoint flags (their loaders read a GPT tree)."""
    for flag, dest in _MODEL_CONFIG_SHAPE_FLAGS:
        if getattr(args, dest) != parser.get_default(dest):
            raise SystemExit(
                f"{flag} shapes the GPT family; --model-config "
                f"{args.model_config} carries the model's shape — drop "
                "the flag"
            )
    for flag, value in (("--checkpoint", args.checkpoint),
                        ("--speculative-draft", args.speculative_draft)):
        if value:
            raise SystemExit(
                f"--model-config {args.model_config} does not compose "
                f"with {flag}: the checkpoint loaders restore a GPT "
                "parameter tree; this family serves random weights from "
                "--seed"
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
        return families[model_type](described)
    except (KeyError, NotImplementedError, ValueError) as e:
        raise SystemExit(f"--model-config {args.model_config}: {e}") from e


def synthetic_trace(args) -> list:
    """Deterministic random request set: prompt lengths uniform in
    [min, max], token ids uniform over the vocabulary (0 is reserved
    for padding)."""
    rng = np.random.RandomState(args.seed)
    out = []
    for i in range(args.num_requests):
        n = int(rng.randint(
            args.prompt_len_min, args.prompt_len_max + 1
        ))
        out.append(Request(
            rid=i,
            prompt=rng.randint(
                1, args.vocab_size, size=n
            ).astype(np.int32),
            max_new_tokens=args.max_new_tokens,
        ))
    return out


def synthetic_arrivals(args) -> np.ndarray:
    """Arrival time (seconds) per request under the --arrival-rate /
    --arrival-burst model: Poisson events (exponential inter-arrival
    gaps at the event rate), --arrival-burst requests sharing each
    event's timestamp. Deterministic in --seed (its own RNG stream, so
    adding arrival flags never perturbs the prompt content). Rate 0 is
    the legacy all-at-t=0 trace."""
    if not args.arrival_rate:
        return np.zeros(args.num_requests, np.float64)
    rng = np.random.RandomState(args.seed + 0x5EED)
    n_events = -(-args.num_requests // args.arrival_burst)  # ceil
    gaps = rng.exponential(
        1.0 / args.arrival_rate, size=n_events
    )
    events = np.cumsum(gaps)
    return np.repeat(events, args.arrival_burst)[:args.num_requests]


# GPTConfig fields recorded by the lm CLI (checkpoint_extra) -> the
# serve flag that controls each, for mismatch messages a user can act
# on. max_position is driven by --max-len (the cache length IS the
# position-table length at serve time).
_GPT_CONFIG_FLAGS = {
    "vocab_size": "--vocab-size",
    "dim": "--dim",
    "num_layers": "--layers",
    "num_heads": "--heads",
    "ffn_dim": "--ffn-dim",
    "max_position": "--max-len",
}


def _checkpoint_guard(directory: str, name: str, cfg) -> None:
    """Fail fast, naming the exact field, when the checkpoint's
    recorded model config disagrees with the serve flags — BEFORE any
    engine compiles. Checkpoints without a recorded config (e.g. saved
    by an older run) fall through to the shape guard at load time."""
    from distributed_model_parallel_tpu.checkpointing import (
        checkpoint_metadata,
    )

    try:
        meta = checkpoint_metadata(directory, name)
    except FileNotFoundError as e:
        raise SystemExit(str(e))
    family = meta.get("lm_family")
    if family:
        raise SystemExit(
            f"--checkpoint {directory}: the checkpoint records "
            f"lm_family.model_type={family.get('model_type')!r} "
            "(cli.lm --model-config); --checkpoint restores a GPT "
            "parameter tree, and no loader maps that family's trained "
            "tree onto a serving family's (cli.serve --model-config "
            "serves one from --seed)"
        )
    recorded = meta.get("gpt_config")
    if not recorded:
        return
    if int(recorded.get("num_experts", 0)) > 0:
        raise SystemExit(
            f"--checkpoint {directory}: the checkpoint is a "
            f"Mixture-of-Experts GPT (num_experts="
            f"{recorded['num_experts']}); --checkpoint restores a GPT "
            "parameter tree into dense GPT decoder blocks, which have "
            "no expert layer (an expert layer serves through "
            "--model-config, from --seed)"
        )
    for field, flag in _GPT_CONFIG_FLAGS.items():
        if field not in recorded:
            continue
        want = getattr(cfg, field)
        got = recorded[field]
        if int(got) != int(want):
            raise SystemExit(
                f"--checkpoint {directory}: the checkpoint was trained "
                f"with {field}={got} but the serve flags give "
                f"{field}={want} — adjust {flag} to match the trained "
                "model"
            )


def _draft_config(args, target_cfg) -> "tuple[GPTConfig, str | None]":
    """Resolve the draft GPT's config for speculative decoding.

    With --speculative-draft, the dims come from the checkpoint's
    recorded gpt_config (the PR-8 checkpoint_extra record) — a draft is
    a DIFFERENT model, so no serve flag describes it; compatibility
    with the target (same vocabulary, position table covering
    --max-len) is checked here, before any engine compiles. Without a
    checkpoint, the draft is a fresh-init layers-truncated twin of the
    target. Returns (config, checkpoint name or None)."""
    if not args.speculative_draft:
        import dataclasses

        layers = args.speculative_draft_layers or max(
            1, args.layers // 2
        )
        return dataclasses.replace(
            target_cfg, num_layers=layers
        ), None
    from distributed_model_parallel_tpu.checkpointing import (
        checkpoint_metadata,
    )
    from distributed_model_parallel_tpu.training.checkpoint import (
        newest_checkpoint_name,
    )

    name = newest_checkpoint_name(args.speculative_draft)
    try:
        meta = checkpoint_metadata(args.speculative_draft, name)
    except FileNotFoundError as e:
        raise SystemExit(str(e))
    recorded = meta.get("gpt_config")
    if not recorded:
        raise SystemExit(
            f"--speculative-draft {args.speculative_draft}: the "
            "checkpoint has no recorded gpt_config, so the draft's "
            "dims are unknowable from flags — re-save it with a "
            "current trainer (checkpoint_extra records the config)"
        )
    if int(recorded.get("num_experts", 0)) > 0:
        raise SystemExit(
            f"--speculative-draft {args.speculative_draft}: the draft "
            f"is a Mixture-of-Experts GPT (num_experts="
            f"{recorded['num_experts']}); the draft's loader restores a "
            "GPT parameter tree into dense GPT decoder blocks, which "
            "have no expert layer (an expert layer serves through "
            "--model-config, from --seed)"
        )
    if int(recorded["vocab_size"]) != target_cfg.vocab_size:
        raise SystemExit(
            f"--speculative-draft {args.speculative_draft}: draft "
            f"vocab_size {recorded['vocab_size']} != target "
            f"vocab_size {target_cfg.vocab_size} — speculative "
            "acceptance compares the two models' distributions over "
            "the SAME vocabulary"
        )
    if int(recorded["max_position"]) < args.max_len:
        raise SystemExit(
            f"--speculative-draft {args.speculative_draft}: draft "
            f"max_position {recorded['max_position']} < --max-len "
            f"{args.max_len} — the draft cache mirrors the target's "
            "positions, so its position table must cover them"
        )
    return GPTConfig(
        vocab_size=int(recorded["vocab_size"]),
        dim=int(recorded["dim"]),
        num_layers=int(recorded["num_layers"]),
        num_heads=int(recorded["num_heads"]),
        ffn_dim=int(recorded["ffn_dim"]),
        max_position=int(recorded["max_position"]),
        dropout_rate=0.0,
        pad_token_id=0,
    ), name


def main(argv=None) -> dict:
    parser = build_parser()
    args = parser.parse_args(argv)
    enable_compile_cache()
    check_serving_args(args)
    model_cfg = None
    if args.model_config:
        model_cfg = _model_config(args, parser)
        args.vocab_size = model_cfg.vocab_size  # the synthetic prompts
    from distributed_model_parallel_tpu.cli.common import (
        setup_metrics_out,
    )

    setup_metrics_out(args.metrics_out)  # fail fast on a bad directory
    if args.trace_out:
        # Fail BEFORE any engine compiles: a mistyped directory must
        # not surface as a lost trace after the whole run.
        import os

        trace_dir = os.path.dirname(os.path.abspath(args.trace_out))
        if not os.path.isdir(trace_dir):
            raise SystemExit(
                f"--trace-out {args.trace_out}: directory "
                f"{trace_dir} does not exist"
            )
    if args.prompt_len_min < 1 or args.prompt_len_max < args.prompt_len_min:
        raise SystemExit(
            f"--prompt-len-min/max must satisfy 1 <= min <= max, got "
            f"[{args.prompt_len_min}, {args.prompt_len_max}]"
        )
    # Chunked prefill ingests in place, so only the cache caps prompt
    # length; monolithic prefill pads to one --prefill-len compile.
    prompt_cap = (
        args.max_len - 1 if args.prefill_chunk else args.prefill_len
    )
    if args.prompt_len_max > prompt_cap:
        raise SystemExit(
            f"--prompt-len-max {args.prompt_len_max} exceeds "
            + (f"--max-len - 1 = {prompt_cap}" if args.prefill_chunk
               else f"--prefill-len {prompt_cap}")
        )
    initialize_backend()
    cfg = model_cfg or GPTConfig(
        vocab_size=args.vocab_size,
        dim=args.dim,
        num_layers=args.layers,
        num_heads=args.heads,
        ffn_dim=args.ffn_dim or 4 * args.dim,
        max_position=args.max_len,
        dropout_rate=0.0,
        pad_token_id=0,
    )
    ckpt_name = None
    if args.checkpoint:
        # THE resume-preference rule, shared with the Trainer: serving
        # must load the same snapshot a resumed training run would.
        from distributed_model_parallel_tpu.training.checkpoint import (
            newest_checkpoint_name,
        )

        ckpt_name = newest_checkpoint_name(args.checkpoint)
        _checkpoint_guard(args.checkpoint, ckpt_name, cfg)
    shards = max(args.model_shards, args.seq_shards)
    mesh = None
    if args.layout != "replicated":
        devices = jax.devices()
        if shards > len(devices):
            raise SystemExit(
                f"{shards} shards requested but only {len(devices)} "
                "devices present"
            )
        mesh = make_mesh(
            MeshSpec(
                data=1,
                model=args.model_shards,
                seq=args.seq_shards,
            ),
            devices=devices[:shards],
        )
    try:
        engine = ServingEngine(
            cfg, mesh,
            layout=args.layout,
            num_slots=args.num_slots,
            max_len=args.max_len,
            prefill_len=args.prefill_len,
            collective_matmul=args.collective_matmul,
            compute_dtype=serve_compute_dtype(args),
            page_size=args.page_size or None,
            num_pages=args.kv_pages or None,
            prefill_chunk=args.prefill_chunk or None,
            prefix_cache=args.prefix_cache,
            speculative_k=args.speculative_k,
        )
    except ValueError as e:
        if model_cfg is None:
            raise
        # a family's refusals, by the option's name
        raise SystemExit(f"--model-config {args.model_config}: {e}") from e
    if engine.latent_dim and jax.process_index() == 0:
        spec = engine.paged_spec
        print(
            f"==> {engine.family.name}: the cache holds one latent row "
            f"of {spec.latent_dim} values a token a layer (stored as "
            f"{spec.latent_width}) and nothing per head: "
            f"{spec.num_pages} pages of {spec.page_size} over "
            f"{spec.num_layers} layers, "
            f"{spec.num_pages * spec.page_bytes / 1e9:.3f} GB stored",
            flush=True,
        )
    if engine.heads_major and jax.process_index() == 0:
        spec, held = engine.paged_spec, getattr(cfg, "experts_held", None)
        print(
            f"==> {engine.family.name}: the cache holds {spec.num_pages} "
            f"heads-major pages of {spec.page_size} "
            f"{'rotated ' if engine.rotary else ''}keys and values, "
            f"{spec.num_heads} heads of {spec.head_dim}, over "
            f"{spec.num_layers} layers "
            f"({spec.num_pages * spec.page_bytes / 1e9:.3f} GB)"
            + (f", and {engine.state_spec.slot_bytes / 1e6:.2f} MB of "
               f"state a slot over {len(engine.state_spec.layers)} layers"
               if engine.state_spec else "")
            + (f"; experts {held[0]}-{held[1] - 1} of "
               f"{cfg.num_experts} held" if held else ""),
            flush=True,
        )
    if engine.chunk_state_program and jax.process_index() == 0:
        print(
            f"==> {engine.family.name}: a chunk of {engine.prefill_chunk} "
            f"positions runs the state layers' recurrence as the "
            f"{engine.chunk_state_program}",
            flush=True,
        )
    if engine.chunk_decay_kind and jax.process_index() == 0:
        print(
            f"==> {engine.family.name}: a chunk of {engine.prefill_chunk} "
            f"positions runs the delta rule's "
            f"{engine.chunk_decay_kind}-decay path",
            flush=True,
        )
    draft_engine = draft_params = None
    if args.speculative_k:
        draft_cfg, draft_ckpt = _draft_config(args, cfg)
        # The draft mirrors every target layout knob (speculative.py's
        # check_draft_engine enforces the cache-shape ones) EXCEPT
        # prefix_cache: prefix pages are a target-side shortcut — the
        # draft always ingests prompts itself.
        draft_engine = ServingEngine(
            draft_cfg, mesh,
            layout=args.layout,
            num_slots=args.num_slots,
            max_len=args.max_len,
            prefill_len=args.prefill_len,
            collective_matmul=args.collective_matmul,
            compute_dtype=serve_compute_dtype(args),
            page_size=args.page_size or None,
            num_pages=args.kv_pages or None,
            prefill_chunk=args.prefill_chunk or None,
        )
        if draft_ckpt is not None:
            import jax.numpy as jnp

            from distributed_model_parallel_tpu.checkpointing import (
                restore_subtree,
            )

            key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
            d_aval, _ = jax.eval_shape(
                draft_engine._full.init, key_aval
            )
            try:
                draft_raw, _ = restore_subtree(
                    args.speculative_draft, d_aval, name=draft_ckpt,
                )
            except (FileNotFoundError, KeyError, ValueError) as e:
                raise SystemExit(
                    f"--speculative-draft {args.speculative_draft}: {e}"
                )
            draft_params = draft_engine.place_params(draft_raw)
            if jax.process_index() == 0:
                print(
                    f"==> speculative draft "
                    f"{args.speculative_draft} ({draft_ckpt}, "
                    f"{draft_cfg.num_layers} layers, k="
                    f"{args.speculative_k})",
                    flush=True,
                )
        else:
            # Fresh-init draft: a real deployment trains/distills one;
            # this keeps the full speculative path exercisable from
            # the CLI with no checkpoint on disk.
            draft_params = draft_engine.init_params(
                jax.random.PRNGKey(args.seed + 1)
            )
    if args.checkpoint:
        import jax.numpy as jnp

        from distributed_model_parallel_tpu.checkpointing import (
            restore_subtree,
        )

        # The trained TrainState's `params` subtree, reassembled to the
        # canonical (host-complete) form from either on-disk layout,
        # then placed into THIS engine's replicated/TP/SP layout — the
        # same dense-twin pytree every training engine produces.
        key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
        p_aval, _ = jax.eval_shape(engine._full.init, key_aval)
        try:
            raw, meta = restore_subtree(
                args.checkpoint, p_aval, name=ckpt_name,
            )
        except (FileNotFoundError, KeyError, ValueError) as e:
            # Shape-level guard for checkpoints with no recorded
            # config: still fails fast, naming the offending leaf.
            raise SystemExit(
                f"--checkpoint {args.checkpoint}: {e}"
            )
        params = engine.place_params(raw)
        if jax.process_index() == 0:
            print(
                f"==> serving checkpoint {args.checkpoint} "
                f"({ckpt_name}, epoch {meta.get('epoch')}, "
                f"format {meta.get('format')})",
                flush=True,
            )
    else:
        params = engine.init_params(jax.random.PRNGKey(args.seed))
    requests = synthetic_trace(args)
    if args.trace_out:
        from distributed_model_parallel_tpu.observability import trace

        trace.enable()
    sampling = None
    if args.temperature > 0:
        from distributed_model_parallel_tpu.serving.sampling import (
            SamplingConfig,
        )

        sampling = SamplingConfig(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed,
        )
    sched = engine.run(
        params, requests, sampling=sampling,
        draft=draft_engine, draft_params=draft_params,
    )
    report = sched.latency_report()
    arrivals = synthetic_arrivals(args)
    if args.arrival_rate:
        # Offered load vs achieved goodput: how much decode the trace
        # ASKED for per second vs the useful fraction of slot-steps
        # the engine actually ran. span = last arrival + one mean
        # inter-event gap (the last burst still wants its tokens), so
        # offered load stays finite even for a single burst.
        span = float(arrivals[-1]) + 1.0 / args.arrival_rate
        offered_req_s = args.num_requests / span
        report["offered_load"] = {
            "arrival_rate": args.arrival_rate,
            "arrival_burst": args.arrival_burst,
            "offered_req_per_s": round(offered_req_s, 3),
            "offered_tokens_per_s": round(
                offered_req_s * args.max_new_tokens, 3
            ),
            "goodput": report.get("goodput"),
            "achieved_tokens_per_s": report.get("tokens_per_s"),
        }
        if jax.process_index() == 0:
            print(
                f"==> offered load "
                f"{report['offered_load']['offered_tokens_per_s']} "
                f"tok/s ({args.arrival_rate} ev/s x "
                f"{args.arrival_burst}/burst) vs achieved "
                f"{report.get('tokens_per_s')} tok/s, goodput "
                f"{report.get('goodput')}",
                flush=True,
            )
    if args.metrics_out:
        from distributed_model_parallel_tpu.cli.common import (
            export_metrics_out,
        )

        export_metrics_out(args.metrics_out)
    if args.trace_out and jax.process_index() == 0:
        from distributed_model_parallel_tpu.observability import trace

        trace.get_tracer().export(args.trace_out)
        print(f"==> wrote Chrome trace to {args.trace_out}",
              flush=True)
    per_request = [
        {
            "rid": f.rid,
            "prompt_len": f.prompt_len,
            "generated": len(f.tokens),
            # The greedy token ids themselves: what a trained
            # --checkpoint run is judged by (parity vs an in-process
            # restore is pinned in tests/test_cli.py).
            "tokens": [int(t) for t in f.tokens],
            "prefill_ms": round(f.prefill_s * 1e3, 3),
            "total_ms": round(f.total_s * 1e3, 3),
        }
        for f in sched.finished
    ]
    out = {
        "serving": {
            "layout": args.layout,
            "model_config": args.model_config,
            "checkpoint": args.checkpoint,
            "shards": shards,
            "collective_matmul": args.collective_matmul,
            "num_slots": args.num_slots,
            "max_len": args.max_len,
            "prefill_len": args.prefill_len,
            "page_size": args.page_size or None,
            "prefill_chunk": args.prefill_chunk or None,
            "prefix_cache": args.prefix_cache,
            "temperature": args.temperature,
            "speculative_k": args.speculative_k or None,
            "speculative_draft": args.speculative_draft,
            **report,
        },
        "requests": per_request,
    }
    if jax.process_index() == 0:
        print(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    main()
