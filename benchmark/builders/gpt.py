"""Configuration file -> the program's objects, for the GPT family
(`models/gpt.GPTConfig`, `ServingEngine`, the `cli.lm` command line).

The file's top-level sizes carry the source's key names (`n_embd`,
`n_layer`, ...); this module is the only place that maps them onto the
program's spelling.
"""

from __future__ import annotations

import copy
from typing import List

# Toy widths for --rehearsal (CPU, tests only): the control flow of a
# cell at a size whose numbers mean nothing. Lengths, page size and
# chunking stay as configured so the traffic files apply unchanged.
REHEARSAL = {
    "top": {"vocab_size": 384, "n_embd": 64, "n_layer": 2, "n_head": 4},
    "serving": {"num_slots": 4, "num_pages": 256},
    "training": {"batch_size": 8, "seq_len": 64},
}


def rehearse(config: dict) -> dict:
    out = copy.deepcopy(config)
    out.update(REHEARSAL["top"])
    for section in ("serving", "training"):
        if section in out:
            out[section].update(REHEARSAL[section])
    return out


def shape(config: dict) -> dict:
    """The sizes FLOP counting and the reference need, `n_inner` filled
    in by the family's convention where the source leaves it null."""
    keys = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head")
    out = {k: int(config[k]) for k in keys}
    out["n_inner"] = int(config.get("n_inner") or 4 * out["n_embd"])
    return out


# Bytes of one element at rest, and what `ServingEngine` keeps its K/V
# cache in under each `compute_dtype` (int8 quantises inside the decode
# projections only: cache and weights stay float32 at rest).
ELEMENT_BYTES = {"float32": 4, "bfloat16": 2}
CACHE_DTYPE = {"f32": "float32", "bf16": "bfloat16", "int8": "float32"}


def serving_widths(config: dict) -> dict:
    """Bytes of a stored weight and of a cached key or value as the
    configuration states them; a spelling this table lacks is an error,
    never a default."""
    return {
        "weight_bytes": ELEMENT_BYTES[config["precision"]["parameters"]],
        "cache_bytes": ELEMENT_BYTES[
            CACHE_DTYPE[config["serving"]["compute_dtype"]]
        ],
    }


def gpt_config(config: dict, max_position: int):
    from distributed_model_parallel_tpu.models.gpt import GPTConfig

    s = shape(config)
    if max_position > s["n_positions"]:
        raise ValueError(
            f"{max_position} positions asked of a model with "
            f"n_positions={s['n_positions']}"
        )
    # dropout 0 and padding id 0 are what cli.serve and cli.lm build.
    return GPTConfig(
        vocab_size=s["vocab_size"], dim=s["n_embd"],
        num_layers=s["n_layer"], num_heads=s["n_head"],
        ffn_dim=s["n_inner"], max_position=max_position,
        dropout_rate=0.0, pad_token_id=0,
    )


def serving_engine(config: dict):
    """The engine `cli.serve.main` would build for these settings (the
    replicated layout takes no mesh)."""
    from distributed_model_parallel_tpu.serving.engine import ServingEngine

    s = config["serving"]
    if s["layout"] != "replicated":
        raise NotImplementedError(
            f"layout {s['layout']!r}: this builder places one replica on "
            "one chip; a sharded layout needs its mesh built here"
        )
    return ServingEngine(
        gpt_config(config, s["max_len"]), None,
        layout=s["layout"],
        num_slots=s["num_slots"],
        max_len=s["max_len"],
        compute_dtype=s["compute_dtype"],
        page_size=s["page_size"],
        num_pages=s["num_pages"],
        prefill_chunk=s["prefill_chunk"],
        prefix_cache=s["prefix_cache"],
    )


def lm_argv(config: dict, traffic: dict, seed: int, out_dir: str) -> List[str]:
    """The `cli.lm` command line of a training cell. One epoch is asked
    for; the benchmark's Trainer runs as many as the window holds."""
    s, t = shape(config), config["training"]
    argv = [
        "--vocab-size", str(s["vocab_size"]), "--dim", str(s["n_embd"]),
        "--layers", str(s["n_layer"]), "--heads", str(s["n_head"]),
        "--ffn-dim", str(s["n_inner"]), "--seq-len", str(t["seq_len"]),
        "--dtype", t["dtype"], "-b", str(t["batch_size"]),
        "--optimizer", t["optimizer"], "--lr", str(t["lr"]),
        "--attention", t["attention"],
        "--corpus-tokens", str(traffic["corpus_tokens"]),
        "--corpus-seed", str(seed),
        "--epochs", "1",
        "--steps-per-epoch", str(traffic["steps_per_epoch"]),
        "--log-file", f"{out_dir}/train.txt",
        "--checkpoint-dir", f"{out_dir}/checkpoint",
    ]
    if t["plan"]:
        argv += ["--plan", t["plan"]]
    if t["remat"]:
        argv.append("--remat")
    return argv
