"""Configuration file -> the program's objects, for the GLM-4 MoE "lite"
family (`models/glm_moe.py`, served through `cli.serve --model-config`
and `ServingEngine`): latent-attention mixers with a compressed query
and rotary positions, a dense SiLU-gated MLP in the leading layer, then
sparse expert layers with one shared expert, an untied head.

The file carries the source's own keys letter for letter. Only depth is
cut (`CUT`): every width, all routed experts, the experts a token picks
and the whole vocabulary are as published. Everything the harness knows
of the family is here: `shape`, `rehearse`, `check`, `period`,
`leading_dense`, `reference_args`, `serving_engine`, `serving_widths`,
`decode_step_cost` (the experts a step's picks are EXPECTED to reach,
the live latent rows), `chunk_prefill_cost`, `slot_rows`,
`step_experts` (the experts a step of the engine took for a row, out of
the cache tree it handed back), `latent_readings` (which rows of the cache are a sequence's first
layer's, and how far what the engine's programs left there lies from
the reference's; the router's picks beside the reference's) and
`picks_reading` (the expert layers' own count of picks against the
rows that were real), and `train_flops_per_token` (counted, though the
program cannot train this family yet).

`python3 benchmark/builders/glm_moe.py FILE` prints the count of
parameters and bytes at rest of a benchmark file.
"""

from __future__ import annotations

import copy
import json
from typing import Dict, Tuple

# What the release publishes for every width (config.json of
# zai-org/GLM-4.7-Flash): a file of this family that states another is
# refused.
WIDTHS = {
    "hidden_size": 2048, "intermediate_size": 10240,
    "moe_intermediate_size": 1536, "num_attention_heads": 20,
    "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256, "n_routed_experts": 64,
    "n_shared_experts": 1, "num_experts_per_tok": 4,
    "routed_scaling_factor": 1.8, "vocab_size": 154880,
}
# The counts a configuration of this family may cut
# (`harness/cut.py`): depth alone.
CUT = {"depth": "num_hidden_layers"}
ELEMENT_BYTES = {"float32": 4, "bfloat16": 2}
COMPUTE_DTYPE = {"float32": "f32", "bfloat16": "bf16"}

# Toy widths for --rehearsal (CPU, tests only): a dense layer and two
# expert layers at a size whose numbers mean nothing, in float32
# throughout so that the comparisons with the reference read rounding
# only. Pages and max_len stay as configured (the mix's documents have
# to fit); the chunk is a quarter, so that the check's document and a
# rehearsal are seconds (the grouped product runs in the interpreter on
# the CPU).
REHEARSAL = {
    "top": {
        "vocab_size": 384, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
        "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": 16, "num_experts_per_tok": 4,
        "num_hidden_layers": 3,
    },
    "precision": {"parameters": "float32", "activations": "float32"},
    "serving": {"num_slots": 4, "num_pages": 1024, "prefill_chunk": 256},
    # float32 against float32 reads 1e-6: limits of its own, so that the
    # tests see each comparison fail on the fault it is there for (the
    # file's limits are set on the chip, for bfloat16 at full width)
    "tolerance": {"serve_logits": 1e-4, "shared_prefix": 1e-4,
                  "router_regret": 1e-5, "latent_rows": 1e-4,
                  "router_picks": 0.0, "moe_picks": 0.0},
}


def rehearse(config: dict) -> dict:
    out = copy.deepcopy(config)
    out.update(REHEARSAL["top"])
    for section in ("precision", "serving", "tolerance"):
        out[section].update(REHEARSAL[section])
    return out


def period(config: dict) -> int:
    """Every layer after the leading dense ones is the same."""
    return 1


def leading_dense(config: dict) -> int:
    return int(config["first_k_dense_replace"])


def check(config: dict) -> None:
    """What only this family's files have to satisfy: every width, the
    routed experts, the picks a token and the vocabulary as published
    (the rehearsal's toy widths are the tests' alone and never pass
    here), and at least one expert layer."""
    for key, want in WIDTHS.items():
        if config.get(key) != want:
            raise ValueError(
                f"{config['name']}: {key} is {config.get(key)!r}; the "
                f"release publishes {want!r} and it is never cut"
            )
    if int(config["num_hidden_layers"]) <= leading_dense(config):
        raise ValueError(
            f"{config['name']}: {config['num_hidden_layers']} layers "
            "leave the model without an expert layer"
        )


def shape(config: dict) -> dict:
    """The sizes counting and the readers need; the drivers read
    `vocab_size` alone. `builder` lets a reader find this file again."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    rq, rank, nope, rope, dv = (int(config[k]) for k in (
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim"))
    f, fe = (int(config[k]) for k in (
        "intermediate_size", "moe_intermediate_size"))
    layers = int(config["num_hidden_layers"])
    dense = min(leading_dense(config), layers)
    return {
        "builder": "glm_moe",
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": d, "heads": h, "rank": rank, "nope": nope,
        "rope": rope, "dv": dv, "latent_row": rank + rope,
        "layers": layers, "dense_layers": dense,
        "expert_layers": layers - dense,
        "experts": int(config["n_routed_experts"]),
        "picks": int(config["num_experts_per_tok"]),
        "shared_experts": int(config["n_shared_experts"]),
        # weights a token is multiplied by
        "mixer_matmul": (
            d * rq + rq * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + h * dv * d
        ),
        "mixer_norms": rq + rank,
        "dense_matmul": 3 * d * f,
        "expert_matmul": 3 * d * fe,
        "router_matmul": d * int(config["n_routed_experts"]),
        "norms": 2 * d,
    }


def layer_params(config: dict) -> Dict[str, int]:
    """Parameters of one dense layer, of one expert layer, and of the
    embedding with the head and the final norm."""
    s = shape(config)
    mixer = s["mixer_matmul"] + s["mixer_norms"] + s["norms"]
    return {
        "dense_layer": mixer + s["dense_matmul"],
        "expert_layer": (
            mixer + (s["experts"] + s["shared_experts"]) * s["expert_matmul"]
            + s["router_matmul"] + s["experts"]  # the correction bias
        ),
        "embedding_and_head": (
            2 * s["vocab_size"] * s["hidden_size"] + s["hidden_size"]
        ),
    }


def param_count(config: dict) -> int:
    s, per = shape(config), layer_params(config)
    return (
        s["dense_layers"] * per["dense_layer"]
        + s["expert_layers"] * per["expert_layer"]
        + per["embedding_and_head"]
    )


def reference_args(config: dict) -> dict:
    """Keywords of `reference.forward` beside `(params, ids)`."""
    nope, rope = (
        int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"]))
    return {"arch": {
        "heads": int(config["num_attention_heads"]),
        "rank": int(config["kv_lora_rank"]), "nope": nope, "rope": rope,
        "dv": int(config["v_head_dim"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "top_k": int(config["num_experts_per_tok"]),
        "routed_scale": float(config["routed_scaling_factor"]),
    }}


def serving_widths(config: dict) -> dict:
    """Bytes of a stored weight and of a cached latent value, as the
    configuration states them."""
    p = config["precision"]
    return {
        "weight_bytes": ELEMENT_BYTES[p["parameters"]],
        "cache_bytes": ELEMENT_BYTES[p["activations"]],
    }


def latent_token_bytes(config: dict) -> int:
    """Bytes one token's rows take over all layers, before the device's
    tiling pads a row."""
    s = shape(config)
    return (
        s["layers"] * s["latent_row"] * serving_widths(config)["cache_bytes"]
    )


def experts_reached(config: dict, rows: float) -> float:
    """Routed experts that `rows` rows' picks are EXPECTED to reach in
    one expert layer, a uniform router assumed."""
    s = shape(config)
    e = s["experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** (s["picks"] * rows))


def _weights_read(config: dict, rows: float) -> float:
    """Parameters a step over `rows` rows has to read but for the
    embedding and the head: every mixer, the dense layer, each expert
    layer's router, shared expert and the experts its picks reach."""
    s = shape(config)
    per_layer = s["mixer_matmul"] + s["mixer_norms"] + s["norms"]
    return (
        s["layers"] * per_layer + s["dense_layers"] * s["dense_matmul"]
        + s["expert_layers"] * (
            s["router_matmul"] + s["experts"]
            + (s["shared_experts"] + experts_reached(config, rows))
            * s["expert_matmul"]
        )
    )


def _row_matmul(s: dict) -> int:
    """Weights of all blocks that every row is multiplied by."""
    return (
        s["layers"] * s["mixer_matmul"]
        + s["dense_layers"] * s["dense_matmul"]
        + s["expert_layers"] * (
            s["router_matmul"]
            + (s["picks"] + s["shared_experts"]) * s["expert_matmul"]
        )
    )


def decode_step_cost(config: dict, slots: float,
                     live_tokens: float) -> Tuple[float, float]:
    """(operations, HBM bytes) of one decode step that advances `slots`
    sequences of `live_tokens` cached positions each, attention
    ABSORBED: the weights the step reads (the head once, of the
    embedding the rows it takes, of each expert layer the experts its
    `slots` x picks are expected to reach), every live latent row once,
    the logits out."""
    s, w = shape(config), serving_widths(config)
    head = s["vocab_size"] * s["hidden_size"]
    fold = s["heads"] * s["rank"] * (s["nope"] + s["dv"])
    attention = s["layers"] * s["heads"] * (
        2 * s["rank"] + s["rope"]
    ) * live_tokens
    operations = slots * 2.0 * (
        _row_matmul(s) + head + s["layers"] * fold + attention
    )
    nbytes = (
        (_weights_read(config, slots) + head + slots * s["hidden_size"])
        * w["weight_bytes"]
        + slots * live_tokens * latent_token_bytes(config)
        + slots * s["vocab_size"] * 4
    )
    return operations, nbytes


def chunk_prefill_cost(config: dict, chunk: float,
                       start: float) -> Tuple[float, float]:
    """(operations, HBM bytes) of one chunked-prefill step over `chunk`
    positions of one prompt that begin at position `start`, attention
    EXPANDED: the blocks' products on every position, every head's keys
    and values made from the rows up to the chunk's end, causal
    attention over them, the head on ONE row; the weights the chunk's
    picks reach once, the slot's latent rows up to the chunk's end
    once."""
    s, w = shape(config), serving_widths(config)
    head = s["vocab_size"] * s["hidden_size"]
    pairs = chunk * start + chunk * (chunk + 1) / 2.0  # query-key pairs
    expand = (start + chunk) * s["rank"] * s["heads"] * (s["nope"] + s["dv"])
    attend = pairs * s["heads"] * (s["nope"] + s["rope"] + s["dv"])
    operations = 2.0 * (
        chunk * _row_matmul(s) + head + s["layers"] * (expand + attend)
    )
    nbytes = (
        (_weights_read(config, chunk) + head + chunk * s["hidden_size"])
        * w["weight_bytes"]
        + (start + chunk) * latent_token_bytes(config)
        + s["vocab_size"] * 4
    )
    return operations, nbytes


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward (twice the forward) of one token of a
    `seq_len` causal sequence, averaged over it, attention expanded.
    Counted for the harness's sake: `cli.lm` cannot train this family
    yet."""
    s = shape(config)
    expand = s["rank"] * s["heads"] * (s["nope"] + s["dv"])
    attend = s["heads"] * (s["nope"] + s["rope"] + s["dv"]) * (
        seq_len + 1) / 2.0
    forward = 2.0 * (
        _row_matmul(s) + s["vocab_size"] * s["hidden_size"]
        + s["layers"] * (expand + attend)
    )
    return 3.0 * forward


def program_config(config: dict) -> dict:
    """The keys `models/glm_moe.config_from_dict` reads: the file's own,
    and the dtype its weights rest in under the release's name for it."""
    return {**config, "torch_dtype": config["precision"]["parameters"]}


def serving_engine(config: dict):
    """The engine `cli.serve --model-config` builds for these settings
    (the replicated layout takes no mesh)."""
    from distributed_model_parallel_tpu.models.glm_moe import config_from_dict
    from distributed_model_parallel_tpu.serving.engine import ServingEngine

    s = config["serving"]
    return ServingEngine(
        config_from_dict(program_config(config)), None,
        layout=s["layout"],
        num_slots=s["num_slots"],
        max_len=s["max_len"],
        compute_dtype=COMPUTE_DTYPE[config["precision"]["activations"]],
        page_size=s["page_size"],
        num_pages=s["num_pages"],
        prefill_chunk=s["prefill_chunk"],
        prefix_cache=s["prefix_cache"],
    )


def slot_rows(cache: dict, bt_row, n_tokens: int):
    """The FIRST layer's cached rows of one sequence's first `n_tokens`
    positions, (n_tokens, row) float32 on the host, out of a cache tree
    as a step of the engine handed it back and the sequence's row of
    the block table."""
    import numpy as np

    pool = cache["latent"]["0"]
    page = pool.shape[1]
    pages = np.asarray(bt_row)[: -(-n_tokens // page)]
    rows = np.asarray(pool[pages], np.float32)
    return rows.reshape(-1, rows.shape[-1])[:n_tokens]


def step_experts(cache: dict, kind: str, row: int):
    """The experts every expert layer took for one row of the step
    that handed `cache` back, (expert layers, k) on the host: `kind` is
    the step's ("decode": the row is a slot; "chunk": a position of the
    chunk). What the step itself routed by (`models/moe.py`'s
    `moe_chosen`, kept in the cache tree beside the counters)."""
    import numpy as np

    return np.asarray(cache["counters"][f"moe_chosen_{kind}"][row])


def _distance(got, want) -> float:
    import numpy as np

    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def latent_readings(config: dict, reference, params, ids, held,
                    control=None) -> Dict[str, float]:
    """What the cell holds beside the logits, {name: reading}:

    `latent_rows`: the first layer's rows that the engine's own
    programs left in the pool for the tokens `ids` (`held`, from
    `slot_rows`: chunk after chunk of the prompt, then a decode step a
    token) against the reference's rows on the inputs a model computes
    that rounds what its matrices take and give to the configuration's
    activations (`reference.latent_rows` with `handed_on`): the larger
    of the compressed part's and the rotary part's distance, each a
    norm of the difference over the norm. No layer lies before the
    first, so what is left between the two is the row itself: a key
    cached unrotated, rotated twice or at another position, rotated in
    less than float32, normalised late, a chunk's tail written over the
    next token's row.
    `router_picks`: the share of the tokens' rows whose chosen experts
    differ between `models/moe.route` and the reference's router at the
    first expert layer, on the same float32 inputs.

    `control` (a dtype, with `held` None) puts the reference in that
    precision in the program's place: its rows with the rotation in it,
    its router in it."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.models import moe

    args = reference_args(config)
    arch = args["arch"]
    act = jnp.finfo(jnp.dtype(config["precision"]["activations"]))
    handed_on = functools.partial(
        jax.lax.reduce_precision,
        exponent_bits=act.nexp, mantissa_bits=act.nmant)
    ids = np.asarray(ids)
    want = np.asarray(jax.jit(functools.partial(
        reference.latent_rows, handed_on=handed_on, **args
    ))(params, ids[None]))[0]
    if held is None:
        low = jnp.finfo(jnp.dtype(control))
        rounded = functools.partial(
            jax.lax.reduce_precision,
            exponent_bits=low.nexp, mantissa_bits=low.nmant)
        # the rotation's angles and products in the control's precision
        held = np.asarray(jax.jit(functools.partial(
            reference.latent_rows, handed_on=handed_on,
            rotation=rounded, **args
        ))(params, ids[None]))[0]
    rank, row = arch["rank"], arch["rank"] + arch["rope"]

    @jax.jit
    def differing(params, ids):
        flat, router_w, bias = reference.router_case(params, ids, **args)
        if control is None:
            got, _ = moe.route(
                flat, router_w, bias.astype(jnp.float32), arch["top_k"],
                arch["routed_scale"])
            got = jnp.sort(got, axis=-1)
        else:
            got = reference.picks(
                flat, router_w, bias, router_dtype=control, **args)
        want = reference.picks(flat, router_w, bias, **args)
        return jnp.mean(jnp.any(got != want, axis=-1))

    return {
        "latent_rows": max(
            _distance(held[:, :rank], want[:, :rank]),
            # (a stored row ends in zeros up to whole lane tiles)
            _distance(held[:, rank:row], want[:, rank:row]),
        ),
        "router_picks": float(differing(params, ids[None])),
    }


def picks_reading(config: dict, paged: dict, real_rows: int) -> float:
    """How far the expert layers' count of routed picks (`moe_picks` of
    a run's `paged_stats`, summed on the device by the steps) lies from
    what `real_rows` real rows give (prompt positions the chunks
    ingested and slots the decode steps advanced; every expert layer
    routes each to `num_experts_per_tok` experts), as a share of it:
    0 exactly, unless rows that are not real (a chunk's padded tail, an
    inactive slot) were routed, multiplied and counted."""
    s = shape(config)
    want = real_rows * s["expert_layers"] * s["picks"]
    return abs(paged["moe_picks"] - want) / want


if __name__ == "__main__":
    import sys

    with open(sys.argv[1]) as f:
        described = json.load(f)
    count = param_count(described)
    at_rest = ELEMENT_BYTES[described["precision"]["parameters"]]
    print(json.dumps({
        "parameters": count, "bytes_at_rest": count * at_rest,
        "latent_bytes_a_token": latent_token_bytes(described),
        **layer_params(described),
    }))
