#!/usr/bin/env python3
"""Kimi-Linear at the published widths on the chip, beyond the cell's
own check: the program (bfloat16 compute, flash kernels, chunked delta
rule, sorted grouped experts) against the plain reference, and the same
numbers for a lower-precision control.

  python3 experiments/kimi_linear_compare.py --seed 2900000301

A. One sequence of the cell's length: the largest logit difference over
   the reference's largest logit; the gradient of the mean next-token
   loss against `jax.grad` of the reference's: global norms, the norm of
   the difference over the reference's norm, and the cosine leaf by
   leaf. Then the same for the controls: the reference itself with the
   delta rule's state, or the router's scores, in bfloat16.
B. The cell's batch: the program's loss and the reference's controls',
   each as a distance from the reference's loss: what
   `tolerance.train_loss` cannot tell apart.
C. The cell's batch: the builder's `precision_readings` (what the
   driver `train_job_precision` holds every run to) for the program
   and with the lower-precision reference in its place: what
   `tolerance.kda_recurrence` and `.router_picks` lie between.

`--parts` picks among them (default all; C alone is under a minute).
Writes one JSON object to `--out` (default
chiprun_out/kimi_linear_compare.json) and prints it. `--toy` runs the
builder's rehearsal widths on the CPU (for a dry run; its numbers mean
nothing). One process: it alone touches JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=2900000301)
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--parts", default="abc")
    ap.add_argument("--c-seeds", type=int, default=1,
                    help="part C on this many seeds, from --seed up")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "kimi_linear_compare.json"))
    args = ap.parse_args(argv)

    from benchmark.harness import manifest
    from benchmark.harness.device import seed_key

    with open(os.path.join(
            ROOT, "benchmark", "configs", "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    builder = manifest.load_module("builder", config["builder"])
    ref = manifest.load_module("reference", config["reference"])
    if args.toy:
        from distributed_model_parallel_tpu.runtime.platform import force_cpu

        force_cpu(1)
        config = builder.rehearse(config)

    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import kimi_linear as kl
    from distributed_model_parallel_tpu.models.layers import Context
    from distributed_model_parallel_tpu.ops.pallas_attention import (
        flash_attention,
    )

    t0 = time.perf_counter()
    say = lambda msg: print(
        f"[compare {time.perf_counter() - t0:6.1f}s] {msg}",
        file=sys.stderr, flush=True)
    if not args.toy and jax.devices()[0].platform != "tpu":
        print("kimi_linear_compare: no TPU (use --toy for a dry run)",
              file=sys.stderr)
        return 2

    cfg = kl.config_from_dict(builder.program_config(config))
    arch = builder.reference_args(config)["arch"]
    train = config["training"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[train["dtype"]]
    seq, batch = train["seq_len"], train["batch_size"]

    model = kl.kimi_linear_lm(
        cfg, attention_fn=partial(flash_attention, causal=True), remat=True)

    def mean_loss(logits, ids):
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        return -jnp.mean(picked), logits

    def main_fn(params, state, ids):
        logits, _ = model.apply(
            params, state, ids, Context(train=True, dtype=dtype))
        return mean_loss(logits, ids)

    controls = (
        ("reference_state_bf16", {"state_dtype": jnp.bfloat16}),
        ("reference_router_bf16", {"router_dtype": jnp.bfloat16}),
    )
    key = seed_key(args.seed)
    params, state = jax.jit(model.init)(key)
    ids = jax.random.randint(
        jax.random.fold_in(key, 1), (batch, seq), 1, cfg.vocab_size)
    say(f"weights made: {sum(x.size for x in jax.tree_util.tree_leaves(params))} parameters")

    def ref_loss(p, i, **control):
        total, count = ref.next_token_loss(p, i, arch, **control)
        return total / count

    result = {}
    # ---------------------------------------------- C: stated precision
    if "c" in args.parts:
        programs = {
            "program": builder.precision_program(config, ref),
            "control": builder.precision_program(config, ref, control=True),
        }
        by_seed = {}
        for seed in range(args.seed, args.seed + args.c_seeds):
            k = seed_key(seed)
            p, _ = jax.jit(model.init)(k)
            i = jax.random.randint(
                jax.random.fold_in(k, 1), (batch, seq), 1, cfg.vocab_size)
            by_seed[str(seed)] = {
                name: {m: float(v) for m, v in read(p, i).items()}
                for name, read in programs.items()}
            say(f"C seed {seed}: {by_seed[str(seed)]}")
            del p
        result["stated_precision"] = {
            "batch": [batch, seq],
            "limits": {k: config["tolerance"][k]
                       for k in ("kda_recurrence", "router_picks")},
            "by_seed": by_seed,
        }

    # ---------------------------------------------- B: the cell's batch
    if "b" in args.parts:
        losses = {"reference": float(jax.jit(ref_loss)(params, ids))}
        say(f"B reference loss {losses['reference']:.6f}")
        losses["program"] = float(jax.jit(
            lambda p, s, i: main_fn(p, s, i)[0])(params, state, ids))
        say(f"B program {losses['program']:.6f}")
        for name, control in controls:
            losses[name] = float(jax.jit(
                partial(ref_loss, **control))(params, ids))
            say(f"B {name} {losses[name]:.6f}")
        result["cell_batch"] = {
            "batch": [batch, seq], "loss": losses,
            "distance_from_reference": {
                k: abs(v - losses["reference"])
                for k, v in losses.items() if k != "reference"},
        }

    # ----------------------------------------------- A: one sequence
    if "a" in args.parts:
        result["one_sequence"] = one_sequence(
            jax, jnp, ref, arch, params, state, ids[:1], main_fn,
            mean_loss, controls, say)

    result.update({
        "seed": args.seed, "toy": args.toy, "parts": args.parts,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "seconds": time.perf_counter() - t0,
    })
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


def one_sequence(jax, jnp, ref, arch, params, state, one, main_fn,
                 mean_loss, controls, say) -> dict:
    """Part A: logits and gradients of one sequence against the
    reference's, for the program and for the reference's controls."""
    def ref_loss_and_logits(p, i, **control):
        return mean_loss(ref.forward(p, i, arch, **control), i)

    (want_loss, want_logits), want_grads = jax.jit(jax.value_and_grad(
        ref_loss_and_logits, has_aux=True))(params, one)
    top = float(jnp.abs(want_logits).max())
    say(f"A reference loss {float(want_loss):.6f}, largest logit {top:.4f}")

    def norm(tree):
        return float(jnp.sqrt(sum(
            jnp.sum(jnp.square(x.astype(jnp.float32)))
            for x in jax.tree_util.tree_leaves(tree))))

    def against_reference(fn):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            fn, has_aux=True))(params)
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        cosines = {}
        for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want_grads)):
            g, w = g.astype(jnp.float32).ravel(), w.ravel()
            cosines[jax.tree_util.keystr(path)] = float(
                jnp.dot(g, w) / (jnp.linalg.norm(g) * jnp.linalg.norm(w)
                                 + 1e-30))
        worst = sorted(cosines.items(), key=lambda kv: kv[1])[:5]
        diff = jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b, grads, want_grads)
        return {
            "loss": float(loss),
            "loss_distance": abs(float(loss) - float(want_loss)),
            "logit_error_over_largest_logit": float(
                jnp.abs(logits - want_logits).max()) / top,
            "grad_norm": norm(grads),
            "grad_difference_over_reference_norm": norm(diff) / norm(
                want_grads),
            "cosine_min": worst[0][1],
            "cosine_mean": sum(cosines.values()) / len(cosines),
            "cosine_worst_leaves": worst,
            "leaves": len(cosines),
        }

    out = {
        "sequence": list(one.shape),
        "reference": {"loss": float(want_loss), "largest_logit": top,
                      "grad_norm": norm(want_grads)},
    }
    runs = [("program", lambda p: main_fn(p, state, one))] + [
        (name, lambda p, c=control: ref_loss_and_logits(p, one, **c))
        for name, control in controls]
    for name, fn in runs:
        out[name] = against_reference(fn)
        say(f"A {name}: logits "
            f"{out[name]['logit_error_over_largest_logit']:.5f}, grad diff "
            f"{out[name]['grad_difference_over_reference_norm']:.5f}, "
            f"min cosine {out[name]['cosine_min']:.5f}")
    return out


if __name__ == "__main__":
    sys.exit(main())
