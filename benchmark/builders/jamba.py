"""Configuration file -> the program's objects, for the Jamba family
(`models/jamba.py`, served through `cli.serve --model-config` and
`ServingEngine`): Mamba-1 state-space layers with a few multi-query
attention layers among them by the release's offset and period, a dense
SiLU-gated MLP in every layer, a tied head.

The file carries the source's own keys letter for letter; nothing of
this family is cut, so there is no `CUT`. Everything the harness knows
of the family is here: `shape`, `rehearse`, `check`, `reference_args`,
`serving_engine`, `serving_widths`, `decode_step_cost`,
`chunk_prefill_cost` (a prompt chunk's operations and bytes, the head on
one row, the slot's state read and written once), `slot_state`,
`state_distances`, `recurrence_reading` and `state_readings` (which
arrays of the cache are a layer's recurrent state, and how far what the
engine's programs left there lies from the reference's), and
`train_flops_per_token` (counted, though the program cannot train this
family yet).

`python3 benchmark/builders/jamba.py FILE` prints the count of
parameters and bytes at rest of a benchmark file.
"""

from __future__ import annotations

import copy
import json
from typing import Dict, List, Tuple

# What the release publishes for every width (config.json of
# ai21labs/AI21-Jamba2-3B): a file of this family that states another
# is refused.
WIDTHS = {
    "hidden_size": 2560, "intermediate_size": 8192,
    "num_attention_heads": 20, "num_key_value_heads": 1,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_dt_rank": 160,
    "mamba_expand": 2, "num_experts": 1, "num_experts_per_tok": 1,
}
ELEMENT_BYTES = {"float32": 4, "bfloat16": 2}
COMPUTE_DTYPE = {"float32": "f32", "bfloat16": "bf16"}
# Arithmetic of the recurrence per (channel, state index) and position:
# delta x A, exp, factor x h, (delta c) x B, the sum, x C, the sum over N.
SCAN_FLOPS = 7

# Toy widths for --rehearsal (CPU, tests only): both kinds of layer at a
# size whose numbers mean nothing, in float32 throughout so that the
# comparisons with the reference read rounding only. Page size and
# chunking stay as configured; lengths are capped so a rehearsal is
# seconds, not minutes.
REHEARSAL = {
    "top": {
        "vocab_size": 384, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 1,
        "num_hidden_layers": 4, "attn_layer_period": 4,
        "attn_layer_offset": 1, "mamba_d_state": 4, "mamba_dt_rank": 8,
    },
    "precision": {"parameters": "float32", "activations": "float32"},
    "serving": {"num_slots": 4, "max_len": 2048, "num_pages": None},
    # float32 against float32 reads 1e-7: limits of its own, so that the
    # tests see each comparison fail on the fault it is there for (the
    # file's limits are set on the chip, for bfloat16 at full width)
    "tolerance": {"serve_logits": 1e-3, "recycled_slot": 1e-6,
                  "ssm_state": 1e-3, "ssm_recurrence": 1e-4},
}


def rehearse(config: dict) -> dict:
    out = copy.deepcopy(config)
    out.update(REHEARSAL["top"])
    for section in ("precision", "serving", "tolerance"):
        out[section].update(REHEARSAL[section])
    return out


def layers(config: dict) -> List[str]:
    """The mixer of each layer, by the release's convention: layer i
    (0-based) attends iff i % attn_layer_period == attn_layer_offset."""
    period, offset = config["attn_layer_period"], config["attn_layer_offset"]
    return [
        "attn" if i % period == offset else "ssm"
        for i in range(int(config["num_hidden_layers"]))
    ]


def check(config: dict) -> None:
    """What only this family's files have to satisfy: every width as
    published, and at least one layer of each kind."""
    for key, want in WIDTHS.items():
        if config.get(key) != want:
            raise ValueError(
                f"{config['name']}: {key} is {config.get(key)!r}; the "
                f"release publishes {want!r} and a width is never cut"
            )
    if set(layers(config)) != {"attn", "ssm"}:
        raise ValueError(
            f"{config['name']}: attn_layer_offset / attn_layer_period "
            "leave the model without one of its two kinds of layer"
        )


def shape(config: dict) -> dict:
    """The sizes counting and the readers need; the drivers read
    `vocab_size` alone. `builder` lets a reader find this file again."""
    kinds = layers(config)
    d = int(config["hidden_size"])
    d_in = int(config["mamba_expand"]) * d
    n, r, k = (int(config[key]) for key in (
        "mamba_d_state", "mamba_dt_rank", "mamba_d_conv"))
    heads, kv = (int(config[key]) for key in (
        "num_attention_heads", "num_key_value_heads"))
    f = int(config["intermediate_size"])
    return {
        "builder": "jamba",
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": d, "d_inner": d_in, "d_state": n, "dt_rank": r,
        "d_conv": k, "heads": heads, "kv_heads": kv, "head_dim": d // heads,
        "intermediate_size": f,
        "ssm_layers": kinds.count("ssm"), "attn_layers": kinds.count("attn"),
        # weights a token is multiplied by, per layer of each kind
        "mlp_matmul": 3 * d * f,
        "ssm_matmul": d * 2 * d_in + d_in * (r + 2 * n) + r * d_in + d_in * d,
        "attn_matmul": 2 * d * d + 2 * d * kv * (d // heads),
        # the rest of a layer: convolution, A_log, D, biases, norms
        "ssm_other": d_in * k + d_in + d_in + d_in * n + d_in + r + 2 * n,
        "norms": 2 * d,
    }


def param_count(config: dict) -> int:
    s = shape(config)
    per_ssm = s["ssm_matmul"] + s["ssm_other"] + s["mlp_matmul"] + s["norms"]
    per_attn = s["attn_matmul"] + s["mlp_matmul"] + s["norms"]
    return (
        s["ssm_layers"] * per_ssm + s["attn_layers"] * per_attn
        + s["vocab_size"] * s["hidden_size"] + s["hidden_size"]
    )


def block_matmul_params(s: dict) -> int:
    """Weights of all blocks that every token is multiplied by."""
    return (
        s["ssm_layers"] * (s["ssm_matmul"] + s["mlp_matmul"])
        + s["attn_layers"] * (s["attn_matmul"] + s["mlp_matmul"])
    )


def reference_args(config: dict) -> dict:
    """Keywords of `reference.forward` beside `(params, ids)`."""
    return {
        "num_heads": int(config["num_attention_heads"]),
        "num_kv_heads": int(config["num_key_value_heads"]),
        "eps": float(config["rms_norm_eps"]),
    }


def serving_widths(config: dict) -> dict:
    """Bytes of a stored weight, of a cached key or value, and of an
    element of the recurrent state, as the configuration states them."""
    p = config["precision"]
    return {
        "weight_bytes": ELEMENT_BYTES[p["parameters"]],
        "cache_bytes": ELEMENT_BYTES[p["activations"]],
        "state_bytes": ELEMENT_BYTES["float32"],
    }


def slot_state_bytes(config: dict) -> int:
    """Bytes one sequence's state takes over all Mamba layers: the
    recurrence's (d_in x N) in float32 and the convolution's last
    K - 1 inputs in the activations' dtype."""
    s, w = shape(config), serving_widths(config)
    per_layer = (
        s["d_inner"] * s["d_state"] * w["state_bytes"]
        + (s["d_conv"] - 1) * s["d_inner"] * w["cache_bytes"]
    )
    return s["ssm_layers"] * per_layer


def _scan_flops(s: dict, positions: float) -> float:
    return (
        SCAN_FLOPS * positions * s["ssm_layers"] * s["d_inner"] * s["d_state"]
    )


def decode_step_cost(config: dict, slots: float,
                     live_tokens: float) -> Tuple[float, float]:
    """(operations, HBM bytes) of one decode step that advances `slots`
    sequences of `live_tokens` cached positions each: every weight once
    (the tied embedding is the head's matrix), the attention layers'
    live keys and values once, every advancing slot's state in and out,
    the logits out."""
    s, w = shape(config), serving_widths(config)
    head = s["vocab_size"] * s["hidden_size"]
    attention = 4 * s["attn_layers"] * s["heads"] * s["head_dim"] * live_tokens
    operations = slots * (
        2.0 * (block_matmul_params(s) + head) + attention
    ) + _scan_flops(s, slots)
    kv = (
        2 * s["attn_layers"] * s["kv_heads"] * s["head_dim"] * live_tokens
        * slots * w["cache_bytes"]
    )
    nbytes = (
        param_count(config) * w["weight_bytes"] + kv
        + 2.0 * slots * slot_state_bytes(config)
        + slots * s["vocab_size"] * 4
    )
    return operations, nbytes


def chunk_prefill_cost(config: dict, chunk: float,
                       start: float) -> Tuple[float, float]:
    """(operations, HBM bytes) of one chunked-prefill step over `chunk`
    positions of one prompt that begin at position `start`: the blocks'
    products on every position, causal attention over the cached prefix
    and the chunk itself, the recurrence, the head on ONE row; every
    weight once, the slot's keys and values up to the chunk's end once,
    its state in and out once."""
    s, w = shape(config), serving_widths(config)
    head = s["vocab_size"] * s["hidden_size"]
    pairs = chunk * start + chunk * (chunk + 1) / 2.0  # query-key pairs
    operations = (
        2.0 * chunk * block_matmul_params(s) + 2.0 * head
        + 4 * s["attn_layers"] * s["heads"] * s["head_dim"] * pairs
        + _scan_flops(s, chunk)
    )
    kv = (
        2 * s["attn_layers"] * s["kv_heads"] * s["head_dim"]
        * (start + chunk) * w["cache_bytes"]
    )
    nbytes = (
        param_count(config) * w["weight_bytes"] + kv
        + 2.0 * slot_state_bytes(config) + s["vocab_size"] * 4
    )
    return operations, nbytes


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward (twice the forward) of one token of a
    `seq_len` causal sequence, averaged over it. Counted for the
    harness's sake: `cli.lm` cannot train this family yet."""
    s = shape(config)
    forward = (
        2.0 * (block_matmul_params(s) + s["vocab_size"] * s["hidden_size"])
        + 4 * s["attn_layers"] * s["heads"] * s["head_dim"]
        * (seq_len + 1) / 2.0
        + _scan_flops(s, 1)
    )
    return 3.0 * forward


def program_config(config: dict) -> dict:
    """The keys `models/jamba.config_from_dict` reads: the file's own,
    and the dtype its weights rest in under the release's name for it."""
    return {**config, "torch_dtype": config["precision"]["parameters"]}


def serving_engine(config: dict):
    """The engine `cli.serve --model-config` builds for these settings
    (the replicated layout takes no mesh)."""
    from distributed_model_parallel_tpu.models.jamba import config_from_dict
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


def slot_state(cache: dict, slot: int) -> Dict[int, "object"]:
    """{layer index: that Mamba layer's recurrent state of ONE slot,
    (d_in, N) float32 on the host} out of a cache tree as a step of the
    engine handed it back."""
    import numpy as np

    return {
        int(layer): np.asarray(arrays["h"][slot], np.float32).T
        for layer, arrays in cache["state"].items()
    }


def _distance(got, want) -> float:
    import numpy as np

    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def state_distances(config: dict, reference, params, ids, held: dict,
                    state_dtype=None) -> Dict[int, float]:
    """{layer index: distance of that Mamba layer's state `held` (from
    `slot_state`, after the tokens `ids` (T,)) from the reference's
    after the same tokens, as a share of the reference state's norm}.
    `held` None reads the reference against itself computed with its
    state in `state_dtype`: the control."""
    import functools

    import jax
    import numpy as np

    run = lambda **more: jax.jit(functools.partial(
        reference.forward_with_states, **reference_args(config), **more
    ))(params, ids[None])[1]
    want = {k: np.asarray(v[0]) for k, v in run().items()}
    if held is None:
        held = {
            k: np.asarray(v[0])
            for k, v in run(state_dtype=state_dtype).items()
        }
    if set(held) != set(want):
        raise RuntimeError(
            f"state of layers {sorted(held)} held, the reference has "
            f"{sorted(want)}"
        )
    return {k: _distance(held[k], want[k]) for k in sorted(want)}


def recurrence_reading(config: dict, reference, params, ids, held: dict,
                       state_dtype=None) -> float:
    """The FIRST layer's state that the engine's programs left in the
    pool (`held`, from `slot_state`, after the tokens `ids` (T,))
    against the reference's float32 recurrence, token by token, on the
    inputs a model computes that rounds what its matrices are multiplied
    by to the configuration's activations (`reference.recurrence_case`,
    `handed_on`): norm of the difference over the norm. No layer
    lies before the first, so what is left between the two is the
    recurrence: the precision of its state, step sizes and factors in
    the chunk program and in the decode step, the carry from chunk to
    chunk, the masked tail. `held` None (with `state_dtype`) puts the
    reference's own recurrence in that dtype in the program's place:
    the control."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    act = jnp.finfo(jnp.dtype(config["precision"]["activations"]))
    handed_on = functools.partial(
        jax.lax.reduce_precision,
        exponent_bits=act.nexp, mantissa_bits=act.nmant)

    @jax.jit
    def read(params, ids):
        case = reference.recurrence_case(
            params, ids[None], handed_on=handed_on, **reference_args(config))
        low = (reference.recurrence(*case, state_dtype)[1]
               if state_dtype is not None else None)
        return reference.recurrence(*case)[1], low

    want, low = read(params, np.asarray(ids))
    got = held[min(held)] if held is not None else np.asarray(low[0])
    return _distance(got, np.asarray(want[0]))


def state_readings(config: dict, reference, params, ids, held: dict,
                   state_dtype=None) -> Dict[str, float]:
    """What the cell holds of the recurrent state the engine's programs
    left in a slot (`held`, from `slot_state`) after the tokens `ids`
    (a prompt and the tokens decoded after it), {name: reading}:

    `ssm_state`: the largest of `state_distances` over the Mamba
    layers, against the plain float32 reference. It sees a state that
    was not reset, a padded tail that advanced it, a chunk that did not
    resume the one before. It cannot see the state's precision: the
    bfloat16 activations of the layers before move a state further from
    the reference than a bfloat16 state would (PERF.md, PR 34).
    `ssm_recurrence`: `recurrence_reading`: the first layer's state,
    whose inputs the reference can make as the program does: that is
    what sees a state, step sizes or factors kept in less than float32.

    `held` None (with `state_dtype`) reads the reference in that dtype
    in the program's place, for both: the control."""
    distances = state_distances(
        config, reference, params, ids, held, state_dtype)
    return {
        "ssm_state": max(distances.values()),
        "ssm_recurrence": recurrence_reading(
            config, reference, params, ids, held, state_dtype),
    }


if __name__ == "__main__":
    import sys

    with open(sys.argv[1]) as f:
        described = json.load(f)
    count = param_count(described)
    at_rest = ELEMENT_BYTES[described["precision"]["parameters"]]
    print(json.dumps({
        "parameters": count, "bytes_at_rest": count * at_rest,
        "slot_state_bytes": slot_state_bytes(described),
        "layers": layers(described),
    }))
