#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls,
at GPT-2 small's full width (`models/gpt.GPTConfig` defaults: vocab
50257, dim 768, 12 layers x 12 heads, ffn 3072, 1024 positions; random
weights from a seed), in ONE process:

  barrier           chained bf16 matmul timed with block_until_ready and
                    with a value fetch — are both honest barriers?
  train             cli.lm.main -> Trainer.fit, 6 steps, bf16: loss
                    finite at every step and falling
  train_ring_flash  the same with --attention ring_flash: the Pallas
                    flash kernels (forward + both backward) are in the
                    lowered step; loss follows the XLA leg
  serve             cli.serve.main -> ServingEngine.run on the paged
                    engine: every request finishes; one request's logits
                    at its last prompt position and its first decode
                    step agree with the dense twin `gpt_lm(cfg)`
  serve_int8        the same with --compute-dtype int8: the Pallas int8
                    kernel is in the lowered decode step

and, when JAX sees >= 4 devices, `--plan pp2xdp2` and `--layout tp
--model-shards 4` (the default-mesh train legs are then dp4), each
checked to hold state on four distinct devices. `--extended` adds the
remaining four-chip legs of ISSUE 21 §7.

    python chip_smoke.py                  # on the chip (through chiprun)
    python chip_smoke.py --cpu-rehearsal  # toy widths on the CPU mesh

Exits non-zero, printing no result, when JAX finds no TPU (never a CPU
fallback; the rehearsal is an explicit flag and labels every line) or
when any leg fails. The last stdout line of a passing run on the chip
is `{"ok": true, "device": {"platform", "kind", "count"}}`. Step times here
are fenced after every step by the smoke itself: they say the program
runs, not how fast it is.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Tolerances. Logits: largest difference as a share of the reference
# logits' largest magnitude (the seed weights' top-1 margin is tiny, so
# token ids are not compared). An f32 program runs its TPU matmuls in
# single-pass bf16 by default, so two orders of the same sum differ at
# bf16 level. Each is ~4-5x what the v5e measured (chip run, PR 21:
# 3.8e-3, 2.7e-2; losses 1e-4 apart against a 2e-2 drop per step).
LOGIT_TOL = 2e-2       # paged engine vs dense twin, f32 / prefill legs
INT8_LOGIT_TOL = 1e-1  # int8 decode projections vs the f32 dense twin
LOSS_TOL = 5e-3        # ring_flash vs XLA-attention loss, per step, nats


@dataclasses.dataclass(frozen=True)
class Widths:
    vocab: int
    dim: int
    layers: int
    heads: int
    seq_len: int
    batch: int  # largest power of two whose train step fits one v5e
    lr: float  # constant over the one epoch: low enough not to overshoot
    prompt_min: int
    prompt_max: int
    new_tokens: int
    page: int
    chunk: int
    barrier_n: int


FULL = Widths(50257, 768, 12, 12, 1024, 8, 1e-4, 64, 512, 32, 16, 64, 8192)
TOY = Widths(384, 64, 2, 4, 64, 8, 1e-2, 8, 40, 4, 8, 16, 256)
NUM_SLOTS = NUM_REQUESTS = 8
TRAIN_STEPS = 6


class Smoke:
    """One run: the widths, where output goes, and how lines are
    labelled (every rehearsal line says it is one)."""

    def __init__(self, widths: Widths, out_dir: str, rehearsal: bool):
        self.w = widths
        self.out = out_dir
        self.rehearsal = rehearsal

    def say(self, msg: str) -> None:
        tag = "[platform: cpu, rehearsal] " if self.rehearsal else ""
        print(f"{tag}{msg}", flush=True)

    @contextlib.contextmanager
    def leg(self, name: str):
        """Time one leg; the CLI's own output goes to <out>/<name>/log.txt
        (its tail is shown if the leg raises). Yields (leg_dir, report);
        `report(text)` sets the details of the PASS line."""
        leg_dir = os.path.join(self.out, name)
        os.makedirs(leg_dir, exist_ok=True)
        log_path = os.path.join(leg_dir, "log.txt")
        details = []
        t0 = time.perf_counter()
        try:
            with open(log_path, "w") as log, \
                    contextlib.redirect_stdout(log):
                yield leg_dir, details.append
        except BaseException:
            self.say(f"leg {name}: FAIL after "
                     f"{time.perf_counter() - t0:.1f}s {' '.join(details)} "
                     f"— tail of {log_path}:")
            with open(log_path) as log:
                sys.stderr.write("".join(log.readlines()[-40:]))
            raise
        finally:
            gc.collect()  # drop the leg's device buffers before the next
        self.say(f"leg {name}: PASS wall={time.perf_counter() - t0:.1f}s "
                 + " ".join(details))


# What each kernel leg's call sites look like in a CPU trace, where no
# Mosaic call exists: the flash kernels run interpreted (`pallas_call`);
# the int8 projection takes its dtype-pinned XLA twin (s8 x s8 -> s32).
FLASH_ON_CPU = "pallas_call"
INT8_ON_CPU = "preferred_element_type=int32"


def _kernel_calls(jitted, args, on_cpu: str) -> int:
    """How many kernel call sites the step holds: Mosaic custom calls in
    the lowered text on the chip — so a quiet fallback cannot pass —
    and `on_cpu` equations in the jaxpr in a rehearsal."""
    import jax

    if jax.default_backend() == "tpu":
        return jitted.lower(*args).as_text().count("tpu_custom_call")
    return str(jax.make_jaxpr(jitted)(*args)).count(on_cpu)


def _require_kernels(found: int, per_layer: int, layers: int, report):
    report(f"kernel_sites={found}")
    if found < per_layer * layers:
        raise AssertionError(
            f"the lowered step holds {found} kernel call sites, expected "
            f">= {per_layer} per layer: a fallback took their place"
        )


def _devices_of(tree) -> list:
    import jax

    return sorted({
        shard.device.id
        for leaf in jax.tree_util.tree_leaves(tree)
        for shard in leaf.addressable_shards
    })


def _check_placement(smoke: Smoke, tree, want: int) -> str:
    """Which devices hold `tree`; with `want`, that they are `want`
    distinct devices, each holding bytes."""
    import jax

    used = _devices_of(tree)
    if not want:
        return f"devices={used}"
    if len(used) != want:
        raise AssertionError(
            f"state spans devices {used}, expected {want} distinct"
        )
    if not smoke.rehearsal:  # CPU devices report no memory_stats
        by_id = {d.id: d for d in jax.devices()}
        for i in used:
            if not by_id[i].memory_stats()["bytes_in_use"] > 0:
                raise AssertionError(f"device {i} holds no bytes")
    return f"devices={used}"


def _setup_and_steady(setup_s: float, steady_s, what: str) -> str:
    """A compiled call's first run is set-up (trace + compile + one
    run), reported apart from the median of the later ones."""
    return (f"setup={setup_s:.1f}s "
            f"steady_{what}={statistics.median(steady_s) * 1e3:.1f}ms")


# ------------------------------------------------------------------ legs


def barrier_leg(smoke: Smoke, kind: str) -> None:
    """ROADMAP S3: time 40 chained n^3 bf16 matmuls under each barrier."""
    import jax
    import jax.numpy as jnp

    # the one peak table; unknown = error
    from distributed_model_parallel_tpu.runtime.platform import (
        peak_bf16_flops,
    )

    n = smoke.w.barrier_n
    with smoke.leg("barrier") as (_, report):
        a = jnp.full((n, n), 1e-3, jnp.bfloat16)
        eye = jnp.eye(n, dtype=jnp.bfloat16)

        @jax.jit
        def chain(x):
            for _ in range(8):
                x = x @ eye
            return x

        jax.block_until_ready(chain(a))  # compile + warm
        rates = {"block_until_ready": 0.0, "value_fetch": 0.0}
        for _ in range(2):
            for mode in rates:
                t0 = time.perf_counter()
                y = a
                for _ in range(5):
                    y = chain(y)
                if mode == "block_until_ready":
                    jax.block_until_ready(y)
                else:
                    float(y[0, 0])
                rate = 2 * n ** 3 * 40 / (time.perf_counter() - t0)
                rates[mode] = max(rates[mode], rate)
        block, fetch = rates["block_until_ready"], rates["value_fetch"]
        report(f"block_until_ready={block / 1e12:.1f}TFLOP/s "
               f"value_fetch={fetch / 1e12:.1f}TFLOP/s")
        if smoke.rehearsal:
            return
        peak = peak_bf16_flops(kind)
        if not (block < peak and fetch < peak):
            raise AssertionError(
                f"a barrier returned early: {rates} vs peak {peak:.3g}"
            )
        if abs(block - fetch) > 0.10 * max(block, fetch):
            raise AssertionError(f"the two barriers disagree: {rates}")


@contextlib.contextmanager
def _spied_trainer(lm, kernel_on_cpu):
    """Swap `cli.lm`'s Trainer for one that also keeps, per step, the
    loss and the fenced wall time — the CLI returns epoch means only —
    and the trainer itself, for the placement check; and that writes no
    checkpoint."""
    import jax

    seen = {"losses": [], "step_s": [], "kernels": None, "trainer": None}
    base = lm.Trainer

    class SpiedTrainer(base):
        def __init__(self, engine, train, val, config, **kwargs):
            # No best-accuracy snapshot: at full width it is one ~2 GB
            # file, checked for nothing here, and more than the checking
            # machine lets a process write (EFBIG, driver run, PR 21).
            config = dataclasses.replace(config, save_best=False)
            step = engine.train_step

            def fenced_step(*step_args):
                if kernel_on_cpu and seen["kernels"] is None:
                    seen["kernels"] = _kernel_calls(
                        step, step_args, kernel_on_cpu
                    )
                t0 = time.perf_counter()
                state, metrics = step(*step_args)
                m = jax.device_get(metrics)
                seen["step_s"].append(time.perf_counter() - t0)
                seen["losses"].append(
                    float(m["loss_sum"]) / float(m["count"])
                )
                return state, metrics

            engine.train_step = fenced_step
            super().__init__(engine, train, val, config, **kwargs)
            seen["trainer"] = self

    lm.Trainer = SpiedTrainer
    try:
        yield seen
    finally:
        lm.Trainer = base
        seen["trainer"] = None  # break the cycle holding device state


def train_leg(smoke: Smoke, name: str, extra=(), *, devices: int = 0,
              follow=None, kernels_per_layer: int = 0) -> list:
    """`cli.lm.main` for TRAIN_STEPS steps; returns the per-step losses.
    `follow`: another leg's losses this one must track within LOSS_TOL.
    `kernels_per_layer`: Pallas kernels the lowered step must hold."""
    from distributed_model_parallel_tpu.cli import lm

    w = smoke.w
    with smoke.leg(name) as (leg_dir, report):
        argv = [
            "--vocab-size", str(w.vocab), "--dim", str(w.dim),
            "--layers", str(w.layers), "--heads", str(w.heads),
            "--seq-len", str(w.seq_len), "--dtype", "bfloat16",
            "-b", str(w.batch), "--lr", str(w.lr),
            "--epochs", "1", "--steps-per-epoch", str(TRAIN_STEPS),
            "--log-file", os.path.join(leg_dir, "train.txt"),
            "--checkpoint-dir", os.path.join(leg_dir, "checkpoint"),
            *extra,
        ]
        with _spied_trainer(
            lm, FLASH_ON_CPU if kernels_per_layer else None
        ) as seen:
            result = lm.main(argv)
            placed = _check_placement(
                smoke, seen["trainer"].state, devices
            )
        losses = seen["losses"]
        step_s = seen["step_s"]
        report(f"{_setup_and_steady(step_s[0], step_s[1:], 'step')} {placed} "
               f"loss={'>'.join(f'{x:.3f}' for x in losses)}")
        if len(losses) != TRAIN_STEPS:
            raise AssertionError(f"{len(losses)} steps ran: {losses}")
        if not all(math.isfinite(x) for x in losses):
            raise AssertionError(f"non-finite loss: {losses}")
        start = math.log(w.vocab)
        if abs(losses[0] - start) > 0.5:
            raise AssertionError(
                f"first loss {losses[0]:.3f} is not near ln(vocab) = "
                f"{start:.3f}"
            )
        if not losses[-1] < losses[0]:
            raise AssertionError(f"loss did not fall: {losses}")
        epoch = result["history"][-1]
        if not math.isfinite(epoch["val"]["loss"]):
            raise AssertionError(f"non-finite val loss: {epoch}")
        if kernels_per_layer:
            _require_kernels(
                seen["kernels"], kernels_per_layer, w.layers, report
            )
        if follow is not None:
            gap = max(abs(a - b) for a, b in zip(losses, follow))
            report(f"max_loss_gap_vs_xla={gap:.4f}")
            if gap > LOSS_TOL:
                raise AssertionError(
                    f"loss {losses} left the XLA leg's {follow}"
                )
    return losses


@contextlib.contextmanager
def _spied_engine(serve, prompt, kernel_on_cpu):
    """Swap `cli.serve`'s ServingEngine for one that also keeps, from
    the real run, the logits `prompt`'s request saw at its last prompt
    position and at its first decode step, the fenced time of every
    compiled call, and the engine + params for the dense-twin check."""
    import jax
    import numpy as np

    seen = {"engine": None, "params": None, "cache": None,
            "chunk_s": [], "decode_s": [], "kernels": None,
            "prompt_logits": None, "decode_logits": None,
            "first_page": None}
    base = serve.ServingEngine

    class SpiedEngine(base):
        def run(self, params, requests, **kwargs):
            seen["engine"], seen["params"] = self, params
            chunk_prefill, decode_step = self.chunk_prefill, self.decode_step

            def chunk(params, cache, bt_row, ids, start, n_valid):
                t0 = time.perf_counter()
                cache, logits = chunk_prefill(
                    params, cache, bt_row, ids, start, n_valid
                )
                jax.block_until_ready(logits)
                seen["chunk_s"].append(time.perf_counter() - t0)
                s, n = int(start), int(n_valid)
                if s + n == prompt.size and np.array_equal(
                    np.asarray(ids)[0, :n], prompt[s:]
                ):
                    seen["prompt_logits"] = np.asarray(logits)
                    seen["first_page"] = int(np.asarray(bt_row)[0])
                return cache, logits

            def decode(*args):
                if kernel_on_cpu and seen["kernels"] is None:
                    seen["kernels"] = _kernel_calls(
                        decode_step, args, kernel_on_cpu
                    )
                bt, positions = args[2], args[3]
                t0 = time.perf_counter()
                cache, logits = decode_step(*args)
                jax.block_until_ready(logits)
                seen["decode_s"].append(time.perf_counter() - t0)
                seen["cache"] = cache
                if (seen["first_page"] is not None
                        and seen["decode_logits"] is None):
                    # The slot is the one whose block table starts at
                    # the page the prompt's chunks were written to.
                    (slot,) = np.nonzero(
                        np.asarray(bt)[:, 0] == seen["first_page"]
                    )[0]
                    if int(np.asarray(positions)[slot]) != prompt.size:
                        raise AssertionError(
                            "first decode step not at the prompt's end"
                        )
                    seen["decode_logits"] = np.asarray(logits)[slot]
                return cache, logits

            self.chunk_prefill, self.decode_step = chunk, decode
            return super().run(params, requests, **kwargs)

    serve.ServingEngine = SpiedEngine
    try:
        yield seen
    finally:
        serve.ServingEngine = base
        seen.update(engine=None, params=None, cache=None)


def _dense_twin_rows(engine, params, prompt, first_token: int):
    """The dense twin's logits for `prompt` at its last position, and
    for prompt + first_token at the position after: full-sequence
    recompute, padded to max_len so both are one compiled shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_model_parallel_tpu.models.layers import Context

    twin = engine._full  # gpt_lm(cfg), the tree `params` is
    _, state_aval = jax.eval_shape(
        twin.init, jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    state = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), state_aval
    )

    @jax.jit
    def row(params, ids, at):
        logits, _ = twin.apply(params, state, ids, Context(train=False))
        return logits[0, at]

    n = prompt.size
    ids = np.zeros((1, engine.max_len), np.int32)
    ids[0, :n] = prompt
    at_prompt = np.asarray(row(params, jnp.asarray(ids), n - 1))
    ids[0, n] = first_token
    at_decode = np.asarray(row(params, jnp.asarray(ids), n))
    return at_prompt, at_decode


def serve_leg(smoke: Smoke, name: str, extra=(), *, devices: int = 0,
              decode_tol: float = LOGIT_TOL,
              kernels_per_layer: int = 0) -> None:
    """`cli.serve.main` over NUM_REQUESTS synthetic requests on the paged
    engine, then the logit check of request 0 against the dense twin."""
    import numpy as np

    from distributed_model_parallel_tpu.cli import serve

    w = smoke.w
    with smoke.leg(name) as (_, report):
        argv = [
            "--vocab-size", str(w.vocab), "--dim", str(w.dim),
            "--layers", str(w.layers), "--heads", str(w.heads),
            "--max-len", str(w.seq_len), "--num-slots", str(NUM_SLOTS),
            "--page-size", str(w.page), "--prefill-chunk", str(w.chunk),
            "--num-requests", str(NUM_REQUESTS),
            "--prompt-len-min", str(w.prompt_min),
            "--prompt-len-max", str(w.prompt_max),
            "--max-new-tokens", str(w.new_tokens),
            *extra,
        ]
        prompt = serve.synthetic_trace(
            serve.build_parser().parse_args(argv)
        )[0].prompt
        with _spied_engine(
            serve, prompt, INT8_ON_CPU if kernels_per_layer else None
        ) as seen:
            result = serve.main(argv)
            placed = _check_placement(
                smoke, (seen["params"], seen["cache"]), devices
            )
            got_prompt = seen["prompt_logits"]
            got_decode = seen["decode_logits"]
            want_prompt, want_decode = _dense_twin_rows(
                seen["engine"], seen["params"], prompt,
                int(got_prompt.argmax()),
            )
        chunk_s, decode_s = seen["chunk_s"], seen["decode_s"]
        report(_setup_and_steady(
            chunk_s[0] + decode_s[0], decode_s[1:], "decode_step"
        ) + f" {placed}")
        requests = result["requests"]
        if len(requests) != NUM_REQUESTS or any(
            r["generated"] != w.new_tokens for r in requests
        ):
            raise AssertionError(
                "not every request finished with "
                f"{w.new_tokens} tokens: "
                f"{[r['generated'] for r in requests]}"
            )
        for what, got, want, tol in (
            ("last_prompt_position", got_prompt, want_prompt, LOGIT_TOL),
            ("first_decode_step", got_decode, want_decode, decode_tol),
        ):
            if got.shape != (w.vocab,) or not np.isfinite(got).all():
                raise AssertionError(f"{what}: bad logits {got.shape}")
            err = float(np.abs(got - want).max() / np.abs(want).max())
            report(f"{what}_err={err:.2e}(tol {tol:.0e})")
            if err > tol:
                raise AssertionError(
                    f"{what} logits differ from the dense twin by "
                    f"{err:.3e} of its largest magnitude (tol {tol})"
                )
        if kernels_per_layer:
            _require_kernels(
                seen["kernels"], kernels_per_layer, w.layers, report
            )


# ------------------------------------------------------------------- run


def _cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(name.endswith("-cache") for name in os.listdir(path))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--out", default=os.path.join(HERE, "smoke_out"),
        help="everything the run writes goes here (default: smoke_out/ "
             "beside this file)",
    )
    parser.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="run every leg's code path at toy widths on the virtual "
             "CPU mesh, kernels interpreted; labels every line, proves "
             "nothing about the chip",
    )
    parser.add_argument(
        "--extended", action="store_true",
        help="with >= 4 devices, also run pp2-1f1bxdp2, sp2xdp2, fsdp4 "
             "and tp4 with --collective-matmul (ISSUE 21 §7)",
    )
    args = parser.parse_args(argv)

    try:
        from distributed_model_parallel_tpu.runtime.platform import (
            enable_compile_cache,
            force_cpu,
        )
    except ImportError as e:
        print(f"chip_smoke.py: the package is not importable from "
              f"{HERE}: {e}", file=sys.stderr)
        return 2
    if args.cpu_rehearsal:
        force_cpu(8)
    import jax

    first = jax.devices()[0]
    platform, kind, count = first.platform, first.device_kind, len(
        jax.devices()
    )
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke.py: no accelerator: JAX found platform "
              f"{platform!r} ({count} device(s)); this check runs on a "
              "TPU only (--cpu-rehearsal for the toy-width rehearsal)",
              file=sys.stderr)
        return 2

    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    smoke = Smoke(TOY if args.cpu_rehearsal else FULL, out_dir,
                  args.cpu_rehearsal)
    cache_dir = enable_compile_cache()
    if args.cpu_rehearsal:
        # The cache is for the chip's minutes-long compiles; reloading
        # XLA:CPU executables only adds loader warnings to toy compiles.
        jax.config.update("jax_enable_compilation_cache", False)
    smoke.say(f"jax {jax.__version__} platform: {platform} "
              f"device_kind: {kind!r} devices: {count}")
    smoke.say(f"compile cache: {cache_dir} entries_before="
              f"{_cache_entries(cache_dir)}")
    smoke.say(f"widths: {smoke.w} output: {out_dir}")
    t0 = time.perf_counter()

    barrier_leg(smoke, kind)
    four = count >= 4
    # cli.lm's default mesh is data-parallel over every device.
    mesh = f"dp{count}" if count > 1 else "1chip"
    on_all = count if four else 0
    xla_losses = train_leg(smoke, f"train[{mesh}]", devices=on_all)
    train_leg(smoke, f"train_ring_flash[{mesh}]",
              ("--attention", "ring_flash"), devices=on_all,
              follow=xla_losses, kernels_per_layer=3)
    serve_leg(smoke, "serve")
    serve_leg(smoke, "serve_int8", ("--compute-dtype", "int8"),
              decode_tol=INT8_LOGIT_TOL, kernels_per_layer=4)
    if four:
        pp = ("--microbatches", "4")
        tp = ("--layout", "tp", "--model-shards", "4")
        train_leg(smoke, "train[pp2xdp2]", ("--plan", "pp2xdp2", *pp),
                  devices=4)
        serve_leg(smoke, "serve[tp4]", tp, devices=4)
        if args.extended:
            train_leg(smoke, "train[pp2-1f1bxdp2]",
                      ("--plan", "pp2-1f1bxdp2", *pp), devices=4)
            train_leg(smoke, "train[sp2xdp2]", ("--plan", "sp2xdp2"),
                      devices=4)
            train_leg(smoke, "train[fsdp4]", ("--plan", "fsdp4"),
                      devices=4)
            serve_leg(smoke, "serve[tp4,collective_matmul]",
                      (*tp, "--collective-matmul"), devices=4)
    else:
        smoke.say(f"saw {count} device(s): skipped the four-chip legs "
                  "(train[pp2xdp2], serve[tp4])")

    smoke.say(f"all legs passed in {time.perf_counter() - t0:.1f}s; "
              f"compile cache entries_after={_cache_entries(cache_dir)}")
    if not args.cpu_rehearsal:  # a rehearsal is not a result
        print(json.dumps({"ok": True, "device": {
            "platform": platform, "kind": kind, "count": count,
        }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
