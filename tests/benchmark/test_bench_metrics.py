"""Metric arithmetic on canned records: the end-to-end and per-layer
readers, the percentile rule, the sample-count rule, peaks and FLOPs."""

import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import flops, manifest, peaks, stats  # noqa: E402
from benchmark.harness.trace_reduce import Event  # noqa: E402

SMALL = {"vocab_size": 50257, "n_positions": 1024, "n_embd": 768,
         "n_layer": 12, "n_head": 12, "n_inner": 3072}
XL = {"vocab_size": 50257, "n_positions": 1024, "n_embd": 1600,
      "n_layer": 48, "n_head": 25, "n_inner": 6400}


def reader(kind, name):
    return manifest.load_module(kind, name).compute


def finished(n_tokens, prefill_s, total_s, prompt_len=10, rid=0):
    return {"rid": rid, "prompt_len": prompt_len, "n_tokens": n_tokens,
            "prefill_s": prefill_s, "total_s": total_s}


# ------------------------------------------------------------ arithmetic


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 100])
def test_percentile_is_numpys_linear_rule(q):
    xs = list(np.random.default_rng(0).exponential(1.0, size=101))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("n,q,beyond", [
    (100, 90, 10), (99, 90, 9), (200, 95, 10), (120, 90, 12), (100, 99, 1),
])
def test_samples_beyond_a_percentile(n, q, beyond):
    assert stats.samples_beyond(n, q) == beyond


def test_tpot_is_first_token_to_last_over_the_tokens_after_the_first():
    fins = [
        finished(5, prefill_s=1.0, total_s=1.4),    # 4 gaps in 0.4 s
        finished(2, prefill_s=0.5, total_s=0.53),   # 1 gap of 30 ms
        finished(1, prefill_s=0.2, total_s=0.2),    # no gap: left out
    ]
    assert stats.tpot_ms(fins) == pytest.approx([100.0, 30.0])


def test_saturated_window_ends_with_the_last_first_token():
    fins = [
        finished(5, prefill_s=1.0, total_s=5.0),    # done before the end
        finished(11, prefill_s=2.0, total_s=12.0),  # 1 token a second
        finished(3, prefill_s=6.0, total_s=9.0),    # the last first token
        finished(1, prefill_s=4.0, total_s=4.0),
    ]
    got = stats.saturated(fins)
    assert got["seconds"] == 6.0
    # 5, then 1 + 10 x (6 - 2) / 10, then its first token only, then 1
    assert got["tokens"] == pytest.approx(5 + 5 + 1 + 1)
    assert got["requests_per_s"] == pytest.approx(4 / 6.0)


def test_spread_is_interquartile_distance_over_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(2.0 / 3.0)
    assert stats.spread([7.0] * 6) == 0.0


# ------------------------------------------------------------ end to end


def serving_record():
    fins = [finished(11, 0.5, 0.5 + 0.010 * 10 * (i + 1), rid=i)
            for i in range(100)]  # TPOT of request i = 10 ms x (i + 1)
    return {
        "setup_s": 12.5, "window_s": 20.0, "finished": fins,
        "slots": 4, "decode_steps": 50, "step_occupancy_sum": 150,
        "paged": {"pages_in_use_peak": 48, "num_pages": 64},
        "prefix": {"tokens_reused": 300}, "prompt_tokens": 1000,
        "memory_peak_bytes": 5_500_000_000,
        "shape": SMALL, "device": {"kind": "TPU v5 lite", "count": 1},
        "widths": {"weight_bytes": 4, "cache_bytes": 2},
        "host_spans": [Event("decode_step", float(i), 0.2) for i in range(10)]
        + [Event("prefill_chunk", i + 0.5, 0.1) for i in range(10)]
        + [Event("queued", 0.0, 20.0)],
        "device_trace": {
            "busy_s": 1.5, "window_s": 2.0,
            "program_median_s": {"jit_paged_decode_step": 0.16},
        },
    }


def test_serving_end_to_end_readers():
    rec = serving_record()
    assert reader("end_to_end", "setup_s")(rec) == 12.5
    # every request got its first token at 0.5 s, the end of the
    # saturated part: one token each in half a second
    assert reader("end_to_end", "serve_out_tok_s")(rec) == pytest.approx(200.0)
    tpot = [10.0 * (i + 1) for i in range(100)]
    assert reader("end_to_end", "serve_tpot_p50_ms")(rec) == pytest.approx(
        np.percentile(tpot, 50))
    assert reader("end_to_end", "serve_tpot_p90_ms")(rec) == pytest.approx(
        np.percentile(tpot, 90))


@pytest.mark.parametrize("name,want", [
    ("sched_slot_occupancy", 75.0),
    ("kv_pages_peak_share", 75.0),
    ("prefix_hit_share", 30.0),
    ("serve_host_share", 85.0),      # 2 s + 1 s of spans in a 20 s drain
    ("decode_step_p50_ms", 200.0),
    ("serve_prefill_share", 100.0 / 3.0),
    ("serve_hbm_peak_gb", 5.5),
    ("serve_device_idle_share", 25.0),
])
def test_serving_layer_readers(name, want):
    assert reader("per_layer", name)(serving_record()) == pytest.approx(want)


@pytest.mark.parametrize("widths", [(4, 2), (2, 2), (4, 4)])
def test_decode_step_roofline_is_bytes_over_the_steps_device_time(widths):
    """Over the compiled step's time on the device (0.16 s in the canned
    trace), not over the host's `decode_step` span (0.2 s), and at the
    byte widths the configuration states."""
    rec = serving_record()
    rec["widths"] = {"weight_bytes": widths[0], "cache_bytes": widths[1]}
    slots, live = 3.0, 10 + 11 / 2.0
    least = flops.decode_step_bytes(SMALL, slots, live, *widths) / 819e9
    assert flops.decode_step_flops(SMALL, slots, live) / 197e12 < least
    got = reader("per_layer", "decode_step_roofline")(rec)
    assert got == pytest.approx(100.0 * least / 0.16)


@pytest.mark.parametrize("compute_dtype,parameters,want", [
    ("bf16", "float32", (4, 2)), ("f32", "float32", (4, 4)),
    ("int8", "float32", (4, 4)), ("bf16", "bfloat16", (2, 2)),
])
def test_byte_widths_come_from_the_configuration(compute_dtype, parameters,
                                                  want):
    gpt = manifest.load_module("builder", "gpt")
    got = gpt.serving_widths({
        "precision": {"parameters": parameters},
        "serving": {"compute_dtype": compute_dtype},
    })
    assert (got["weight_bytes"], got["cache_bytes"]) == want


def test_a_width_the_builder_does_not_know_is_an_error():
    gpt = manifest.load_module("builder", "gpt")
    with pytest.raises(KeyError):
        gpt.serving_widths({"precision": {"parameters": "float8"},
                            "serving": {"compute_dtype": "bf16"}})


@pytest.mark.parametrize("name,blank", [
    ("serve_host_share", {"host_spans": []}),
    ("decode_step_p50_ms", {"host_spans": []}),
    ("serve_prefill_share", {"host_spans": []}),
    ("decode_step_roofline", {"device_trace": None}),
    ("decode_step_roofline", {"device_trace": {"program_median_s": {}}}),
    ("serve_device_idle_share", {"device_trace": None}),
    ("prefix_hit_share", {"prefix": None}),
    ("kv_pages_peak_share", {"paged": None}),
    ("serve_hbm_peak_gb", {"memory_peak_bytes": 0}),
    ("coll_time_share", {"device_trace": None}),
    ("coll_exposed_share", {"device_trace": {"coll_total_s": 0.0}}),
])
def test_a_reader_with_nothing_to_read_returns_nothing(name, blank):
    rec = {**serving_record(), **blank}
    assert reader("per_layer", name)(rec) is None


def training_record():
    return {
        "epochs": [
            {"steps": 4, "wall_s": 9.0, "data_s": 0.9, "loss": 9.0, "traced": True},
            {"steps": 4, "wall_s": 4.0, "data_s": 0.04, "loss": 8.0, "traced": False},
            {"steps": 4, "wall_s": 4.0, "data_s": 0.04, "loss": 7.0, "traced": False},
        ],
        "tokens_per_step": 16384, "train_flops_per_token": 0.8e9,
        "memory_peak_bytes": 12_000_000_000,
        "device": {"kind": "TPU v5 lite", "count": 4},
        "device_trace": {"busy_s": 3.9, "window_s": 4.0,
                         "device0_window_s": 4.0, "device0_busy_s": 3.8,
                         "coll_total_s": 1.0, "coll_exposed_s": 0.4,
                         "kernel_s": 0.19},
    }


@pytest.mark.parametrize("kind,name,want", [
    ("end_to_end", "train_tok_s", 16384.0),  # the traced epoch is left out
    ("per_layer", "train_data_share", 1.0),
    ("per_layer", "train_mfu", 100 * 16384 * 0.8e9 / (4 * 197e12)),
    ("per_layer", "train_hbm_peak_gb", 12.0),
    ("per_layer", "train_device_idle_share", 2.5),
    ("per_layer", "coll_time_share", 25.0),
    ("per_layer", "coll_exposed_share", 10.0),
    ("per_layer", "attn_kernel_share", 5.0),
])
def test_training_readers(kind, name, want):
    assert reader(kind, name)(training_record()) == pytest.approx(want)


# ------------------------------------------------------- peaks and FLOPs


def test_the_v5e_is_in_the_peak_table_under_jaxs_name_for_it():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_s) == (197e12, 819e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_an_unknown_device_kind_is_an_error_not_a_default(kind):
    with pytest.raises(ValueError, match="no peaks known"):
        peaks.peaks_for(kind)


@pytest.mark.parametrize("shape,params,matmul", [
    (SMALL, 163.0e6, 123.5e6), (XL, 1.638e9, 1.555e9),
])
def test_parameter_counts_from_shapes(shape, params, matmul):
    assert flops.total_params(shape) == pytest.approx(params, rel=2e-3)
    assert flops.matmul_params(shape) == pytest.approx(matmul, rel=2e-3)


def test_total_params_is_the_programs_own_tree():
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models.gpt import GPTConfig, gpt_lm

    toy = {"vocab_size": 384, "n_positions": 64, "n_embd": 64,
           "n_layer": 2, "n_head": 4, "n_inner": 128}
    cfg = GPTConfig(384, 64, 2, 4, 128, 64, 0.0, 0)
    tree, _ = jax.eval_shape(
        gpt_lm(cfg).init, jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    n = sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(tree))
    assert flops.total_params(toy) == n


def test_train_flops_per_token_small_and_xl():
    # 6 N plus causal attention: 0.742 + 0.057 and 9.33 + 0.47 GFLOP
    assert flops.train_flops_per_token(SMALL, 1024) == pytest.approx(
        0.799e9, rel=2e-3)
    assert flops.train_flops_per_token(XL, 1024) == pytest.approx(
        9.80e9, rel=2e-3)
    # the backward pass costs twice the forward
    assert flops.train_flops_per_token(SMALL, 512) == pytest.approx(
        3 * flops.forward_flops_per_token(SMALL, 512))


def test_decode_step_cost_is_linear_in_slots_and_live_tokens():
    one = flops.decode_step_flops(SMALL, 1, 100)
    assert flops.decode_step_flops(SMALL, 8, 100) == pytest.approx(8 * one)
    b0 = flops.decode_step_bytes(SMALL, 8, 0, 4, 2)
    b1 = flops.decode_step_bytes(SMALL, 8, 500, 4, 2)
    # 500 live tokens x 8 slots x (K and V) x 12 layers x 768 x 2 bytes
    assert b1 - b0 == pytest.approx(500 * 8 * 2 * 12 * 768 * 2)
    assert b0 == pytest.approx(
        flops.matmul_params(SMALL) * 4 + 8 * 50257 * 4)
