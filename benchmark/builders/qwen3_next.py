"""Configuration file -> the program's objects, for the Qwen3-Next family
(`models/qwen3_next.py`, served through `cli.serve --model-config` and
`ServingEngine`): gated delta-rule layers with a matrix state per slot
and gated softmax attention whose keys are rotated in part (one in
`full_attention_interval`), every layer over a softmax-routed expert
layer with a gated shared expert, an untied head.

The file carries the source's own keys letter for letter. Two counts
are cut (`CUT`): depth, and the routed experts this chip HOLDS (a share
of a layer that `deployment_chips_per_layer` chips split); the router
keeps every published expert and its top 10. Everything the harness
knows of the family is here: `shape`, `rehearse`, `check`, `period`,
`leading_dense`, `reference_args`, `program_config`, `serving_engine`,
`serving_widths`, `decode_step_cost` (the held experts a step's picks
are EXPECTED to reach, the live keys and values, the state),
`chunk_prefill_cost`, `slot_state`, `slot_keys`, `step_experts` and
`step_rows` (what the engine's programs left in the cache tree),
`state_readings`, `kv_reading`, `router_reading`, `experts_reading` and
`picks_reading` (how far that lies from the reference), and
`train_flops_per_token` (counted, though the program cannot train this
family yet).

`python3 benchmark/builders/qwen3_next.py FILE` prints the file
`cli.serve --model-config` takes for a benchmark file (`program_config`);
with `--count`, its count of parameters and bytes at rest.
"""

from __future__ import annotations

import copy
import json
from typing import Dict, Tuple

# What the release publishes for every width (config.json of
# Qwen/Qwen3-Next-80B-A3B-Instruct): a file of this family that states
# another is refused.
WIDTHS = {
    "hidden_size": 2048, "num_attention_heads": 16,
    "num_key_value_heads": 2, "head_dim": 256,
    "partial_rotary_factor": 0.25, "linear_num_key_heads": 16,
    "linear_num_value_heads": 32, "linear_key_head_dim": 128,
    "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "num_experts_per_tok": 10, "intermediate_size": 5120,
    "vocab_size": 151936,
}
# The counts a configuration of this family may cut (`harness/cut.py`).
CUT = {"depth": "num_hidden_layers", "experts_held": "num_experts"}
# Keys of the benchmark's own, not the release's.
BENCHMARK_KEYS = (
    "name", "source", "builder", "reference", "reduced", "published",
    "assumed", "precision", "serving", "tolerance", "deployment",
    "deployment_chips_per_layer",
)
ELEMENT_BYTES = {"float32": 4, "bfloat16": 2}
COMPUTE_DTYPE = {"float32": "f32", "bfloat16": "bf16"}

# Toy widths for --rehearsal (CPU, tests only): one period of layers and
# half of 16 experts held, at a size whose numbers mean nothing, in
# float32 throughout so that the comparisons with the reference read
# rounding only. The chunk is an eighth and max_len a sixteenth, so a
# rehearsal is seconds (the driver's `rehearse` shortens the mix to
# match).
REHEARSAL = {
    "top": {
        "vocab_size": 384, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 128,
        "linear_num_key_heads": 2, "linear_num_value_heads": 4,
        "linear_key_head_dim": 16, "linear_value_head_dim": 16,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_experts": 8, "num_experts_per_tok": 4,
        "published": {"num_hidden_layers": 48, "num_experts": 16},
    },
    "precision": {"parameters": "float32", "activations": "float32"},
    "serving": {"num_slots": 4, "max_len": 2048, "prefill_chunk": 256},
    # float32 against float32 reads 1e-6: limits of its own, so that the
    # tests see each comparison fail on the fault it is there for (the
    # file's limits are set on the chip, for bfloat16 at full width)
    "tolerance": {"serve_logits": 1e-4, "recycled_slot": 1e-6,
                  "delta_state": 1e-4, "conv_state": 1e-5, "kv_rows": 1e-4,
                  "router_regret": 1e-5, "router_picks": 0.0,
                  "moe_rows": 1e-4, "moe_picks": 0.0},
}


def rehearse(config: dict) -> dict:
    out = copy.deepcopy(config)
    out.update(copy.deepcopy(REHEARSAL["top"]))
    for section in ("precision", "serving", "tolerance"):
        out[section].update(REHEARSAL[section])
    return out


def period(config: dict) -> int:
    """Delta-rule layers and one attention layer."""
    return int(config["full_attention_interval"])


def leading_dense(config: dict) -> int:
    """Every layer routes (`mlp_only_layers` empty, sparse step 1)."""
    return 0


def check(config: dict) -> None:
    """What only this family's files have to satisfy: every width, the
    router's experts and picks as published (the rehearsal's toy widths
    are the tests' alone and never pass here), whole periods of layers,
    and every layer sparse."""
    for key, want in WIDTHS.items():
        if config.get(key) != want:
            raise ValueError(
                f"{config['name']}: {key} is {config.get(key)!r}; the "
                f"release publishes {want!r} and it is never cut"
            )
    routed = int(config.get("published", {}).get(
        "num_experts", config["num_experts"]))
    if routed != 512:
        raise ValueError(
            f"{config['name']}: the router has {routed} experts; the "
            "release publishes 512 and the router keeps every one"
        )
    if int(config["num_hidden_layers"]) % period(config):
        raise ValueError(
            f"{config['name']}: {config['num_hidden_layers']} layers are "
            "no whole number of periods"
        )
    if config.get("mlp_only_layers") or config.get("decoder_sparse_step") != 1:
        raise ValueError(f"{config['name']}: every layer routes")


def _held(config: dict) -> int:
    return int(config["num_experts"])


def _routed(config: dict) -> int:
    return int(config.get("published", {}).get(
        "num_experts", config["num_experts"]))


def serving_widths(config: dict) -> dict:
    """Bytes of a stored weight and of a cached value, as the
    configuration states them; a state is float32."""
    p = config["precision"]
    return {
        "weight_bytes": ELEMENT_BYTES[p["parameters"]],
        "cache_bytes": ELEMENT_BYTES[p["activations"]],
        "state_bytes": 4,
    }


def shape(config: dict) -> dict:
    """The sizes counting and the readers need; the drivers read
    `vocab_size` alone. `builder` lets a reader find this file again."""
    d = int(config["hidden_size"])
    h, hkv, dh = (int(config[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    hk, hv, dk, dv, taps = (int(config[k]) for k in (
        "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim"))
    fe, fs = (int(config[k]) for k in (
        "moe_intermediate_size", "shared_expert_intermediate_size"))
    layers = int(config["num_hidden_layers"])
    attn = layers // period(config)
    conv = 2 * hk * dk + hv * dv
    expert = 3 * d * fe
    return {
        "builder": "qwen3_next",
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": d, "layers": layers, "attn_layers": attn,
        "gdn_layers": layers - attn, "heads": h, "kv_heads": hkv,
        "head_dim": dh,
        "rot": int(round(float(config["partial_rotary_factor"]) * dh)),
        "hk": hk, "hv": hv, "dk": dk, "dv": dv, "conv": conv,
        "conv_taps": taps,
        "experts_held": _held(config), "experts_routed": _routed(config),
        "picks": int(config["num_experts_per_tok"]),
        # weights a token is multiplied by
        "gdn_matmul": d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv
        + hv * dv * d,
        "gdn_small": taps * conv + 2 * hv + dv,
        "attn_matmul": d * 2 * h * dh + 2 * d * hkv * dh + h * dh * d,
        "attn_small": 2 * dh,
        "router_matmul": d * _routed(config),
        "shared_matmul": 3 * d * fs + d,
        "expert_matmul": expert,
        "norms": 2 * d,
    }


def layer_params(config: dict) -> Dict[str, int]:
    """Parameters of one delta-rule layer, of one attention layer (each
    with its expert layer and the experts held), and of the embedding
    with the head and the final norm."""
    s = shape(config)
    ffn = (s["router_matmul"] + s["shared_matmul"]
           + s["experts_held"] * s["expert_matmul"] + s["norms"])
    return {
        "gdn_layer": s["gdn_matmul"] + s["gdn_small"] + ffn,
        "attn_layer": s["attn_matmul"] + s["attn_small"] + ffn,
        "embedding_and_head": (
            2 * s["vocab_size"] * s["hidden_size"] + s["hidden_size"]
        ),
    }


def param_count(config: dict) -> int:
    s, per = shape(config), layer_params(config)
    return (s["gdn_layers"] * per["gdn_layer"]
            + s["attn_layers"] * per["attn_layer"]
            + per["embedding_and_head"])


def slot_state_bytes(config: dict) -> int:
    """Bytes one sequence's state takes over the delta-rule layers: S
    (hv, dk, dv) and the convolution's last K - 1 inputs, float32."""
    s, w = shape(config), serving_widths(config)
    return s["gdn_layers"] * w["state_bytes"] * (
        s["hv"] * s["dk"] * s["dv"] + (s["conv_taps"] - 1) * s["conv"])


def kv_token_bytes(config: dict) -> int:
    """Bytes one token's keys and values take over the attention
    layers."""
    s = shape(config)
    return (s["attn_layers"] * 2 * s["kv_heads"] * s["head_dim"]
            * serving_widths(config)["cache_bytes"])


def experts_reached(config: dict, rows: float) -> float:
    """HELD experts that `rows` rows' picks are EXPECTED to reach in one
    expert layer, a uniform router assumed: each held expert is among a
    row's `picks` distinct choices of `experts_routed` with probability
    picks / experts_routed."""
    s = shape(config)
    miss = 1.0 - s["picks"] / s["experts_routed"]
    return s["experts_held"] * (1.0 - miss ** rows)


def _row_matmul(s: dict) -> float:
    """Weights of all blocks that every row is multiplied by (the held
    share of its picks: the others are other chips' work)."""
    held_picks = s["picks"] * s["experts_held"] / s["experts_routed"]
    return (
        s["gdn_layers"] * s["gdn_matmul"] + s["attn_layers"] * s["attn_matmul"]
        + s["layers"] * (s["router_matmul"] + s["shared_matmul"]
                         + held_picks * s["expert_matmul"])
    )


def _weights_read(config: dict, rows: float) -> float:
    """Parameters a step over `rows` rows reads but for the embedding
    and the head: every mixer, router and shared expert, and the held
    experts its picks reach."""
    s = shape(config)
    return (
        s["gdn_layers"] * (s["gdn_matmul"] + s["gdn_small"])
        + s["attn_layers"] * (s["attn_matmul"] + s["attn_small"])
        + s["layers"] * (
            s["router_matmul"] + s["shared_matmul"] + s["norms"]
            + experts_reached(config, rows) * s["expert_matmul"])
    )


def _recurrence_flops(s: dict, positions: float, chunked: bool) -> float:
    """The delta rule's products over `positions` of every delta-rule
    layer's heads: one token a step reads, writes and is read by the
    state (4 dk x dv products); in chunks of C = 64 (`ops/delta_rule.py`)
    a chunk makes its triangular products and solves (per position
    4 C dk + 2 C (dk + dv)) and two stacked products with the state
    (4 dk dv + 2 (C + dk) dv)."""
    dk, dv, c = s["dk"], s["dv"], 64.0
    per = (
        4 * c * dk + 2 * c * (dk + dv) + 4 * dk * dv + 2 * (c + dk) * dv
        if chunked else 8.0 * dk * dv
    )
    return s["gdn_layers"] * s["hv"] * positions * per


def decode_step_cost(config: dict, slots: float,
                     live_tokens: float) -> Tuple[float, float]:
    """(operations, HBM bytes) of one decode step that advances `slots`
    sequences of `live_tokens` cached positions each: the weights the
    step reads (the head once, of the embedding the rows it takes, of
    each expert layer the held experts its `slots` x picks are expected
    to reach), every live key and value once, every advancing slot's
    state in and out, the logits out."""
    s, w = shape(config), serving_widths(config)
    head = s["vocab_size"] * s["hidden_size"]
    attention = (4 * s["attn_layers"] * s["heads"] * s["head_dim"]
                 * live_tokens)
    operations = slots * (2.0 * (_row_matmul(s) + head) + attention) + (
        _recurrence_flops(s, slots, chunked=False))
    nbytes = (
        (_weights_read(config, slots) + head + slots * s["hidden_size"])
        * w["weight_bytes"]
        + slots * live_tokens * kv_token_bytes(config)
        + 2 * slots * slot_state_bytes(config)
        + slots * s["vocab_size"] * 4
    )
    return operations, nbytes


def chunk_prefill_cost(config: dict, chunk: float,
                       start: float) -> Tuple[float, float]:
    """(operations, HBM bytes) of one chunked-prefill step over `chunk`
    positions of one prompt that begin at position `start`: the blocks'
    products on every position, causal attention over the prefix and
    the chunk, the delta rule in chunks of 64, the head on ONE row; the
    weights the chunk's picks reach once, the slot's keys and values up
    to the chunk's end once, its state in and out."""
    s, w = shape(config), serving_widths(config)
    head = s["vocab_size"] * s["hidden_size"]
    pairs = chunk * start + chunk * (chunk + 1) / 2.0  # query-key pairs
    attend = 4.0 * s["attn_layers"] * s["heads"] * s["head_dim"] * pairs
    operations = (2.0 * (chunk * _row_matmul(s) + head) + attend
                  + _recurrence_flops(s, chunk, chunked=True))
    nbytes = (
        (_weights_read(config, chunk) + head + chunk * s["hidden_size"])
        * w["weight_bytes"]
        + (start + chunk) * kv_token_bytes(config)
        + 2 * slot_state_bytes(config)
        + s["vocab_size"] * 4
    )
    return operations, nbytes


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward (twice the forward) of one token of a
    `seq_len` causal sequence, averaged over it. Counted for the
    harness's sake: `cli.lm` cannot train this family yet."""
    s = shape(config)
    attend = 4.0 * s["attn_layers"] * s["heads"] * s["head_dim"] * (
        seq_len + 1) / 2.0
    forward = (2.0 * (_row_matmul(s) + s["vocab_size"] * s["hidden_size"])
               + attend + _recurrence_flops(s, 1, chunked=True))
    return 3.0 * forward


def reference_args(config: dict) -> dict:
    """Keywords of `reference.forward` beside `(params, ids)`."""
    s = shape(config)
    return {"arch": {
        "heads": s["heads"], "kv_heads": s["kv_heads"],
        "head_dim": s["head_dim"], "rot": s["rot"],
        "theta": float(config["rope_theta"]),
        "hk": s["hk"], "hv": s["hv"], "dk": s["dk"], "dv": s["dv"],
        "eps": float(config["rms_norm_eps"]), "top_k": s["picks"],
        "experts_held": (0, s["experts_held"]),
    }}


def program_config(config: dict) -> dict:
    """The file `cli.serve --model-config` reads: the source's keys
    with the cut resolved into what the program means by them. There
    `num_experts` is the router's width, as in the release, and
    `experts_held` the range of ids whose weights this chip holds (rank
    0 of the deployment: the first `num_experts` of the file);
    `torch_dtype` the dtype the weights rest in."""
    out = {k: v for k, v in config.items() if k not in BENCHMARK_KEYS}
    out["num_experts"] = _routed(config)
    out["experts_held"] = [0, _held(config)]
    out["torch_dtype"] = config["precision"]["parameters"]
    return out


def serving_engine(config: dict):
    """The engine `cli.serve --model-config` builds for these settings
    (the replicated layout takes no mesh)."""
    from distributed_model_parallel_tpu.models.qwen3_next import (
        config_from_dict,
    )
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


def slot_state(cache: dict, slot: int) -> Dict[str, "object"]:
    """The FIRST layer's (a delta-rule layer's) state of ONE slot out
    of a cache tree as a step of the engine handed it back, float32 on
    the host: {"s": (hv, dk, dv), "conv": (K - 1, channels)}."""
    import numpy as np

    arrays = cache["state"]["0"]
    return {name: np.asarray(arrays[name][slot], np.float32)
            for name in ("s", "conv")}


def slot_keys(cache: dict, bt_row, n_tokens: int):
    """The FIRST attention layer's cached keys of one sequence's first
    `n_tokens` positions, (n_tokens, kv heads, head_dim) float32 on the
    host, out of a cache tree as a step handed it back and the
    sequence's row of the block table (pages heads-major)."""
    import numpy as np

    pool = cache["k"][0]
    page = pool.shape[2]
    pages = np.asarray(bt_row)[: -(-n_tokens // page)]
    held = np.asarray(pool[pages], np.float32).transpose(0, 2, 1, 3)
    return held.reshape(-1, *held.shape[2:])[:n_tokens]


def step_experts(cache: dict, kind: str, row):
    """The experts every layer took for one row of the step that handed
    `cache` back, (layers, k) on the host (rows, layers, k for a slice of
    rows): `kind` is the step's ("decode": the row is a slot; "chunk": a
    position of the chunk)."""
    import numpy as np

    return np.asarray(cache["counters"][f"moe_chosen_{kind}"][row])


def _distance(got, want) -> float:
    import numpy as np

    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _rounding(dtype_name: str):
    import functools

    import jax
    import jax.numpy as jnp

    f = jnp.finfo(jnp.dtype(dtype_name))
    return functools.partial(
        jax.lax.reduce_precision, exponent_bits=f.nexp, mantissa_bits=f.nmant)


def state_readings(config: dict, reference, params, ids, held,
                   state_dtype=None) -> Dict[str, float]:
    """The first delta-rule layer's state that the engine's programs
    left in a slot (`held`, from `slot_state`, after the tokens `ids`)
    against the reference's float32 recurrence token by token on the
    inputs a model computes that rounds what its matrices take and give
    to the configuration's activations (`reference.first_gdn` with
    `handed_on`; no layer lies before the first), norm of the
    difference over the norm: `delta_state` for S, `conv_state` for the
    convolution's kept inputs. What is left between the two is the
    recurrence: its precision in both timed programs, the carry from
    chunk to chunk, a padded tail that must neither decay nor write.
    `held` None (with `state_dtype`) puts the reference's own state in
    that dtype in the program's place: the control."""
    import functools

    import jax
    import numpy as np

    run = lambda **more: jax.jit(functools.partial(
        reference.first_gdn, handed_on=_rounding(
            config["precision"]["activations"]),
        **reference_args(config), **more))(params, np.asarray(ids)[None])
    want = {k: np.asarray(v[0]) for k, v in run().items()}
    if held is None:
        held = {k: np.asarray(v[0])
                for k, v in run(state_dtype=state_dtype).items()}
    return {"delta_state": _distance(held["s"], want["s"]),
            "conv_state": _distance(held["conv"], want["conv"])}


def kv_reading(config: dict, reference, params, ids, keys,
               rotation=None) -> Dict[str, float]:
    """`kv_rows`: the first attention layer's keys that the engine's
    programs left in the pool for the tokens `ids` (`keys`, from
    `slot_keys`: rotated as the chunk program and the decode step
    rotated them) against the reference's rotated keys from its whole
    float32 forward pass: the larger of the rotated part's and the
    rest's distance, each a norm of the difference over the norm. A key
    cached unrotated, rotated at another position or in less than
    float32 moves the rotated part by its whole size; the rest moves by
    what the layers before it drift in the activations' precision.
    `keys` None (with `rotation`, a dtype's name) reads the reference's
    own keys rotated in that precision in the program's place: the
    control."""
    import functools

    import jax
    import numpy as np

    args = reference_args(config)
    run = lambda **more: np.asarray(jax.jit(functools.partial(
        reference.attention_keys, **args, **more))(
            params, np.asarray(ids)[None]))[0]
    want = run()
    if keys is None:
        keys = run(rotation=_rounding(rotation))
    rot = args["arch"]["rot"]
    return {"kv_rows": max(_distance(keys[..., :rot], want[..., :rot]),
                           _distance(keys[..., rot:], want[..., rot:]))}


def step_rows(cache: dict, kind: str, rows) -> Dict[str, "object"]:
    """What the FIRST expert layer of the step that handed `cache` back
    took, gave and chose for `rows` (slots of a decode step, positions
    of a chunk), on the host: {"taken": (n, D), "given": (n, D) float32,
    "chosen": (n, k)} (`models/qwen3_next.FIRST_ROWS`, `moe_chosen`)."""
    import numpy as np

    counters = cache["counters"]
    read = lambda name: np.asarray(counters[f"{name}_{kind}"][rows])
    return {"taken": read("moe_rows_in"), "given": read("moe_rows_out"),
            "chosen": read("moe_chosen")[:, 0]}


def router_reading(config: dict, reference, params, rows,
                   control=None) -> Dict[str, float]:
    """`router_picks`: the share of the rows the timed programs' first
    expert layer took (`rows`, joined `step_rows`) whose experts, as the
    programs chose them, differ from the reference's float32 router's
    choice on the same rows. `control` (a dtype's name) puts the
    reference's router in that precision in the programs' place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    arch = reference_args(config)["arch"]
    router_w = params["blocks"]["0"]["ffn"]["router"]["w"]

    @jax.jit
    def differing(router_w, taken, chosen):
        got = (jnp.sort(chosen, axis=-1) if control is None
               else reference.picks(taken, router_w, arch=arch,
                                    router_dtype=jnp.dtype(control)))
        want = reference.picks(taken, router_w, arch=arch)
        return jnp.mean(jnp.any(got != want, axis=-1))

    return {"router_picks": float(differing(
        router_w, np.asarray(rows["taken"]), np.asarray(rows["chosen"])))}


def experts_reading(config: dict, reference, params,
                    rows) -> Dict[str, float]:
    """`moe_rows`: the rows the timed programs' first expert layer gave
    (`rows`, joined `step_rows`: the chunk program's at 2,048 positions
    and the decode step's at every live slot, grouped and tiled as those
    programs group and tile them) against the reference's expert layer
    (`reference.first_experts`) on the rows the programs' layer took,
    made to take the experts the programs chose: the largest over rows
    of a row's difference norm over its norm. It sees an expert's rows
    lost or weighted wrongly, which moves too few rows of logits to
    show there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    arch = reference_args(config)["arch"]

    @jax.jit
    def farthest(params, taken, given, chosen):
        want = reference.first_experts(params, taken, chosen, arch=arch)
        diff = jnp.linalg.norm(given - want, axis=-1)
        return jnp.max(diff / jnp.linalg.norm(want, axis=-1))

    return {"moe_rows": float(farthest(
        params, *(np.asarray(rows[k]) for k in ("taken", "given",
                                                "chosen"))))}


def picks_reading(config: dict, paged: dict, real_rows: int) -> float:
    """How far the expert layers' count of routed picks (`moe_picks` of
    a run's `paged_stats`, summed on the device by the steps) lies from
    what `real_rows` real rows give (every layer routes each to
    `num_experts_per_tok` experts), as a share of it: 0 exactly, unless
    rows that are not real (a chunk's padded tail, an inactive slot)
    were routed and counted."""
    s = shape(config)
    want = real_rows * s["layers"] * s["picks"]
    return abs(paged["moe_picks"] - want) / want


if __name__ == "__main__":
    import sys

    with open(sys.argv[1]) as f:
        described = json.load(f)
    if "--count" in sys.argv:
        count = param_count(described)
        at_rest = ELEMENT_BYTES[described["precision"]["parameters"]]
        print(json.dumps({
            "parameters": count, "bytes_at_rest": count * at_rest,
            "slot_state_bytes": slot_state_bytes(described),
            "kv_token_bytes": kv_token_bytes(described),
            **layer_params(described),
        }))
    else:
        json.dump(program_config(described), sys.stdout, indent=1)
        print()
