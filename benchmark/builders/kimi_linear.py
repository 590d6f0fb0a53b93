"""Configuration file -> the program's objects, for the Kimi-Linear
family (`models/kimi_linear.py`, trained through `cli.lm
--model-config`): KDA (gated delta rule) and latent-attention mixers by
the published layer lists, a dense SiLU-gated MLP in the leading layer,
one shared plus routed experts after it.

The file carries the source's own keys letter for letter; three counts
are a chip's share of a deployment and are what `CUT` names. Everything
the harness knows of the family is here: `shape`, `rehearse`, `check`,
`lm_argv` (which writes `program_config`: the cut resolved into the
keys the program reads), `reference_args`, `precision_readings` (what
the `precision` block states in float32, held against the reference at
the timed sizes), `train_flops_per_token` (every term written out
below), `kernel_cost` for the per-kernel rooflines, and the pattern
(`period`, `leading_dense`). Serving is not built: the program has
neither a recurrent nor a latent cache.

`python3 benchmark/builders/kimi_linear.py FILE` prints the program's
configuration of a benchmark file, for `cli.lm --model-config`.
"""

from __future__ import annotations

import copy
import json
import os
from typing import Dict, List, Tuple

# The counts a configuration of this family may cut, by kind.
CUT = {"depth": "num_hidden_layers", "experts_held": "num_experts",
       "vocabulary": "vocab_size"}

# What the release publishes for every width (config.json of
# moonshotai/Kimi-Linear-48B-A3B-Instruct): a file of this family that
# states another is refused, cut or not.
WIDTHS = {
    "hidden_size": 2304, "intermediate_size": 9216,
    "moe_intermediate_size": 1024, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_attention_heads": 32, "num_key_value_heads": 32, "head_dim": 72,
    "num_experts_per_token": 8, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446,
}
LINEAR_WIDTHS = {"head_dim": 128, "num_heads": 32,
                 "short_conv_kernel_size": 4}
# The delta rule's chunk in the program (`ops/delta_rule.DEFAULT_CHUNK`),
# which the count of its operations depends on.
CHUNK = 64

# Toy widths for --rehearsal (CPU, tests only): every kind of layer at a
# size whose numbers mean nothing.
REHEARSAL = {
    "top": {
        "vocab_size": 384, "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "kv_lora_rank": 24,
        "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
        "num_attention_heads": 2, "num_key_value_heads": 2, "head_dim": 32,
        "num_experts": 4, "num_experts_per_token": 4,
    },
    "linear_attn_config": {"head_dim": 32, "num_heads": 2},
    "published": {"num_experts": 16, "vocab_size": 3072},
    # the toy learns nothing in six steps at the cell's rate
    "training": {"batch_size": 8, "seq_len": 64, "lr": 0.003},
}


def rehearse(config: dict) -> dict:
    out = copy.deepcopy(config)
    out.update(REHEARSAL["top"])
    for section in ("linear_attn_config", "published", "training"):
        out[section].update(REHEARSAL[section])
    return out


def layers(config: dict) -> List[str]:
    """The mixer of each layer held, from the published 1-based lists
    (their entries past `num_hidden_layers` name layers not built)."""
    lin = config["linear_attn_config"]
    kinds = []
    for layer in range(1, int(config["num_hidden_layers"]) + 1):
        in_kda = layer in lin["kda_layers"]
        if in_kda == (layer in lin["full_attn_layers"]):
            raise ValueError(
                f"{config['name']}: layer {layer} has to be in exactly one "
                "of linear_attn_config.kda_layers and .full_attn_layers"
            )
        kinds.append("kda" if in_kda else "mla")
    return kinds


def period(config: dict) -> int:
    """Layers of one period of the published pattern: the distance
    between two latent-attention layers (3 KDA : 1 MLA gives 4)."""
    full = config["linear_attn_config"]["full_attn_layers"]
    return int(full[1]) - int(full[0])


def leading_dense(config: dict) -> int:
    return int(config["first_k_dense_replace"])


def check(config: dict) -> None:
    """What only this family's files have to satisfy: every width as
    published, the layer lists as published (so that what is held is a
    prefix of the release's pattern), and a mixer for every layer."""
    for key, want in WIDTHS.items():
        if config.get(key) != want:
            raise ValueError(
                f"{config['name']}: {key} is {config.get(key)!r}; the "
                f"release publishes {want!r} and a width is never cut"
            )
    lin = config["linear_attn_config"]
    for key, want in LINEAR_WIDTHS.items():
        if lin.get(key) != want:
            raise ValueError(
                f"{config['name']}: linear_attn_config.{key} is "
                f"{lin.get(key)!r}; the release publishes {want!r}"
            )
    depth = config.get("published", {}).get(
        "num_hidden_layers", config["num_hidden_layers"])
    listed = sorted(lin["kda_layers"] + lin["full_attn_layers"])
    if listed != list(range(1, depth + 1)):
        raise ValueError(
            f"{config['name']}: the two layer lists have to name the "
            f"release's {depth} layers once each, as published"
        )
    layers(config)


def shape(config: dict) -> dict:
    """The sizes counting, the reference and the kernels' readers need;
    the drivers read `vocab_size` alone. `builder` lets a reader find
    `kernel_cost` again."""
    lin = config["linear_attn_config"]
    kinds = layers(config)
    held = int(config["num_experts"])
    return {
        "builder": "kimi_linear",
        "vocab_size": int(config["vocab_size"]),
        "hidden_size": int(config["hidden_size"]),
        "kinds": kinds,
        "kda_num_heads": int(lin["num_heads"]),
        "kda_head_dim": int(lin["head_dim"]),
        "conv_kernel": int(lin["short_conv_kernel_size"]),
        "num_attention_heads": int(config["num_attention_heads"]),
        "qk_head_dim": int(config["qk_nope_head_dim"])
        + int(config["qk_rope_head_dim"]),
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "v_head_dim": int(config["v_head_dim"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "intermediate_size": int(config["intermediate_size"]),
        "moe_intermediate_size": int(config["moe_intermediate_size"]),
        "router_experts": int(
            config.get("published", {}).get("num_experts", held)),
        "experts_held": held,
        "experts_per_token": int(config["num_experts_per_token"]),
        "shared_experts": int(config["num_shared_experts"]),
        "leading_dense": leading_dense(config),
        "batch_size": int(config["training"]["batch_size"]),
        "seq_len": int(config["training"]["seq_len"]),
        "element_bytes": {"bfloat16": 2, "float32": 4}[
            config["training"]["dtype"]],
    }


def reference_args(config: dict) -> dict:
    """Keywords of `reference.forward` / `next_token_loss` beside
    `(params, ids)`: the architecture in the reference's own spelling,
    with the same held experts (rank 0 of the deployment: ids 0 ..
    num_experts - 1)."""
    lin = config["linear_attn_config"]
    depth = int(config["num_hidden_layers"])
    return {"arch": {
        "num_hidden_layers": depth,
        "kda_layers": tuple(i for i in lin["kda_layers"] if i <= depth),
        "full_attn_layers": tuple(
            i for i in lin["full_attn_layers"] if i <= depth),
        "kda_num_heads": int(lin["num_heads"]),
        "kda_head_dim": int(lin["head_dim"]),
        "num_attention_heads": int(config["num_attention_heads"]),
        "qk_nope_head_dim": int(config["qk_nope_head_dim"]),
        "qk_rope_head_dim": int(config["qk_rope_head_dim"]),
        "v_head_dim": int(config["v_head_dim"]),
        "kv_lora_rank": int(config["kv_lora_rank"]),
        "experts_held": (0, int(config["num_experts"])),
        "num_experts_per_token": int(config["num_experts_per_token"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "first_k_dense_replace": leading_dense(config),
        "rms_norm_eps": float(config["rms_norm_eps"]),
    }}


# ------------------------------------------------------------ counting
#
# Matrix-multiply work only, 2 operations a multiply-add (lookups,
# norms, activations, the short convolution's 4 taps and the router's
# top-k are not counted, as for GPT-2: `harness/flops.py`). Causal work
# is counted as the algorithm needs it, held experts at the share of the
# picks they expect, recomputation (`--remat`) never.


def forward_terms(config: dict, seq_len: int) -> Dict[str, float]:
    """Operations of one token's forward pass, averaged over a causal
    sequence of `seq_len`, term by term over the layers held."""
    s = shape(config)
    d = s["hidden_size"]
    h, dk = s["kda_num_heads"], s["kda_head_dim"]
    width = h * dk                      # q, k, v and the gates: 4,096
    n_kda = s["kinds"].count("kda")
    n_mla = s["kinds"].count("mla")
    n_dense = s["leading_dense"]
    n_sparse = len(s["kinds"]) - n_dense
    c = CHUNK
    # KDA projections: q, k, v, out (d x width each way), the decay's
    # and the output gate's low-rank pairs (rank = head width), beta.
    kda_proj = 2.0 * (4 * d * width + 2 * (d * dk + dk * width) + d * h)
    # The chunked delta rule, per head: A (pairs j < i) and B (j <= i)
    # over dk channels, the unit-triangular solve for dv + dk columns,
    # three products with the (dk x dv) state, B U.
    delta = h * (
        (c - 1) * dk + (c + 1) * dk           # A, B
        + (c - 1) * (dk + dk)                 # the solve (dv = dk)
        + 3 * 2 * dk * dk                     # W S, (q e^G) S, K^T U
        + (c + 1) * dk                        # B U
    )
    heads, dqk, dv = (
        s["num_attention_heads"], s["qk_head_dim"], s["v_head_dim"])
    rank, rope = s["kv_lora_rank"], s["qk_head_dim"] - s["qk_nope_head_dim"]
    mla_proj = 2.0 * (
        d * heads * dqk + d * (rank + rope)
        + rank * heads * (s["qk_nope_head_dim"] + dv) + heads * dv * d
    )
    # a query at position t scores t + 1 keys: (T + 1) / 2 on average
    mla_attn = heads * 2.0 * (dqk + dv) * (seq_len + 1) / 2
    expert = 2.0 * 3 * d * s["moe_intermediate_size"]
    held_share = (
        s["experts_per_token"] * s["experts_held"] / s["router_experts"])
    return {
        "kda_projections": n_kda * kda_proj,
        "kda_delta_rule": n_kda * float(delta),
        "mla_projections": n_mla * mla_proj,
        "mla_attention": n_mla * mla_attn,
        "dense_ffn": n_dense * 2.0 * 3 * d * s["intermediate_size"],
        "shared_experts": n_sparse * s["shared_experts"] * expert,
        "router": n_sparse * 2.0 * d * s["router_experts"],
        "held_experts": n_sparse * held_share * expert,
        "head": 2.0 * d * s["vocab_size"],
    }


def train_flops_per_token(config: dict, seq_len: int) -> float:
    """Forward + backward: the backward pass costs twice the forward."""
    return 3.0 * sum(forward_terms(config, seq_len).values())


def kernel_cost(kernel: str, s: dict) -> Tuple[float, float]:
    """(operations, HBM bytes) one training step needs of the Mosaic
    kernels called under the scope `kernel`, forward and backward, from
    `shape(config)`; recomputation not counted.

    "mla": causal flash attention with q.k of `qk_head_dim` and v of
    `v_head_dim`: per (query, key) pair 2 (dqk + dv) forward; backward
    S again, dP, dV (dv each) and dQ, dK (dqk each): 8 dqk + 6 dv in
    all. q, k, v, o, dO, dq, dk, dv cross HBM once. At 192 / 128 and
    2 x 8,192 tokens the OPERATIONS bound it (4.95 TFLOP a layer, 25 ms
    at the v5e's peak, against 1.3 GB, 1.6 ms of traffic).

    "moe": the grouped products of the held experts at the rows they
    expect (tokens x experts_per_token x held / router width), 2 x 3 x
    d x f a row forward and twice that backward; every held expert's
    weights cross HBM three times (forward, the rows' gradient, their
    own gradient written), the rows' activations once each way. The
    OPERATIONS bound it at the cell's sizes, barely."""
    tokens = s["batch_size"] * s["seq_len"]
    e = s["element_bytes"]
    if kernel == "mla":
        n = s["kinds"].count("mla")
        heads, dqk, dv = (
            s["num_attention_heads"], s["qk_head_dim"], s["v_head_dim"])
        pairs = s["batch_size"] * heads * s["seq_len"] * (
            s["seq_len"] + 1) / 2
        return (
            n * pairs * (8.0 * dqk + 6.0 * dv),
            n * tokens * heads * (4.0 * dqk + 4.0 * dv) * e,
        )
    if kernel == "moe":
        n = len(s["kinds"]) - s["leading_dense"]
        d, f = s["hidden_size"], s["moe_intermediate_size"]
        rows = (tokens * s["experts_per_token"] * s["experts_held"]
                / s["router_experts"])
        weights = s["experts_held"] * 3 * d * f
        return (
            n * 3 * rows * 2.0 * 3 * d * f,
            n * (3.0 * weights + 2.0 * rows * (2 * d + 3 * f)) * e,
        )
    raise KeyError(f"no kernel cost for scope {kernel!r}")


# ------------------------------------------------- the program's objects

# Sections of a configuration file that are the benchmark's own; what
# is left are the source's keys.
BENCHMARK_KEYS = (
    "name", "source", "builder", "reference", "reduced", "published",
    "deployment_chips_per_layer", "deployment", "assumed", "precision",
    "training", "tolerance",
)


def program_config(config: dict) -> dict:
    """The file `cli.lm --model-config` reads: the source's keys with
    the cut resolved into what the program means by them. There
    `num_experts` is the router's width, as in the release, and
    `experts_held` the range of ids whose weights this chip holds (rank
    0 of the deployment: the first `num_experts` of the file);
    `vocab_size` and `num_hidden_layers` are what is built."""
    out = {k: v for k, v in config.items() if k not in BENCHMARK_KEYS}
    held = int(config["num_experts"])
    out["num_experts"] = int(
        config.get("published", {}).get("num_experts", held))
    out["experts_held"] = [0, held]
    return out


def lm_argv(config: dict, traffic: dict, seed: int, out_dir: str) -> List[str]:
    """The `cli.lm` command line of a training cell. `--model-config`
    takes a file: `program_config` of the configuration as this run has
    it (the toy one in a rehearsal) is written beside the run's logs.
    One epoch is asked for; the benchmark's Trainer runs as many as the
    window holds."""
    t = config["training"]
    path = os.path.join(out_dir, "model-config.json")
    with open(path, "w") as f:
        json.dump(program_config(config), f)
    argv = [
        "--model-config", path, "--seq-len", str(t["seq_len"]),
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


def precision_readings(config: dict, reference, params, ids,
                       control: bool = False) -> Dict[str, float]:
    """`precision_program` run on the run's initial parameters and
    first batch."""
    read = precision_program(config, reference, control)
    return {k: float(v) for k, v in read(params, ids).items()}


def precision_program(config: dict, reference, control: bool = False):
    """The jitted `(params, ids) -> {name: reading}` behind
    `precision_readings`.

    {name: reading}, each held to `tolerance[name]` by the driver
    `train_job_precision`: what the configuration's `precision` block
    states in float32 inside a bfloat16 step, which the step's loss
    cannot tell (bfloat16 activations move it more). The program's
    float32 part and the reference's get the SAME inputs, made by the
    reference from the run's first batch and initial parameters at the
    sizes the cell times, rounded to the activations' precision where
    the program's layer hands them on in it. Rounded by
    `lax.reduce_precision` and kept float32, so that both sides read
    one array: with a cast to bfloat16 and back inside this program the
    two sides were not rounded alike on the chip (2.0e-3, one rounding
    of q and k, on every seed; the same comparison on inputs rounded
    beforehand read 4.9e-5, and the CPU 3e-7 either way).

    `kda_recurrence`: the chunked delta rule (`ops/delta_rule.py`:
    decay, state, the triangular system; on the TPU also that its
    products run at `highest`, which no CPU test can see) against the
    reference's recurrence token by token, over the first layer's whole
    batch: norm of the difference over the norm, read before the
    result's own rounding to the activations' dtype.
    `router_picks`: the share of rows whose chosen experts differ
    between `models/moe.route` and the reference's router, at the
    first expert layer.

    `control=True` puts the reference in the next precision down (the
    state rounded to bfloat16 after every token; the scores' product in
    bfloat16) in the program's place: the readings the limits have to
    lie under."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models.moe import route
    from distributed_model_parallel_tpu.ops.delta_rule import (
        gated_delta_rule,
    )

    arch = reference_args(config)["arch"]
    act = jnp.finfo(jnp.dtype(config["training"]["dtype"]))
    as_activations = lambda x: jax.lax.reduce_precision(
        x, exponent_bits=act.nexp, mantissa_bits=act.nmant)
    low = jnp.bfloat16

    @jax.jit
    def read(params, ids):
        q, k, v, g, beta = reference.recurrence_case(params, ids, arch)
        q, k, v = as_activations(q), as_activations(k), as_activations(v)
        want = reference.recurrence(q, k, v, g, beta)
        got = (
            reference.recurrence(q, k, v, g, beta, low)
            if control else gated_delta_rule(q, k, v, g, beta)
        )
        norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
        rows, router_w = reference.router_case(params, ids, arch)
        rows = as_activations(rows)
        chosen = reference.picks(rows, router_w, arch)
        if control:
            mine = reference.picks(rows, router_w, arch, low)
        else:
            mine = jnp.sort(route(
                rows, router_w, 0.0, arch["num_experts_per_token"],
                arch["routed_scaling_factor"],
            )[0], axis=-1)
        return {
            "kda_recurrence": norm(got - want) / norm(want),
            "router_picks": jnp.mean(jnp.any(mine != chosen, axis=-1)),
        }

    return read


def _no_serving(what: str):
    raise NotImplementedError(
        f"kimi_linear.{what}: serving this family is not built — "
        "ServingEngine has one paged K/V cache for all layers and needs "
        "a recurrent (KDA state) and a latent (MLA) cache first"
    )


def serving_engine(config: dict):
    _no_serving("serving_engine")


def serving_widths(config: dict) -> dict:
    _no_serving("serving_widths")


def decode_step_cost(config: dict, slots: float, live_tokens: float):
    _no_serving("decode_step_cost")


if __name__ == "__main__":
    import sys

    with open(sys.argv[1]) as _f:
        json.dump(program_config(json.load(_f)), sys.stdout, indent=1)
    print()
