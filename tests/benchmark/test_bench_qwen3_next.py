"""The Qwen3-Next configuration and its cell `qwen3next_serve_longread`:
the file against the catalog row it comes from, the cut against the
rules, the builder's counts against a hand count at the published
widths, the cell's exact metric lists, its `--rehearsal` through
`run.main` at the builder's toy widths, the comparisons
`serve_drain_state_moe` puts behind `correct` (each seen to fail on the
fault it is there for), and the new readers on canned records."""

import functools
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import cut, manifest  # noqa: E402

CELL = "qwen3next_serve_longread"
CONFIG = "qwen3-next-80b-a3b"
# The public catalog's row for the model, as published.
CATALOG = os.path.join(ROOT, "tests", "benchmark", "catalog",
                       "qwen3-next-80b-a3b.jsonl")
ROW = "Qwen3-Next-80B-A3B-Instruct"
READINGS = ("serve_logits", "recycled_slot", "router_regret", "delta_state",
            "conv_state", "kv_rows", "router_picks", "moe_rows", "moe_picks")
PER_LAYER = {
    "sched_slot_occupancy", "kv_pages_peak_share", "serve_host_share",
    "decode_step_p50_ms", "decode_step_roofline", "serve_hbm_peak_gb",
    "serve_device_idle_share", "prefill_chunk_roofline", "prefill_pad_share",
    "state_traffic_share",
}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def builder():
    return manifest.load_module("builder", "qwen3_next")


def test_every_key_of_the_catalog_row_is_in_the_file_but_the_cut(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == ROW)
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if config.get(k) != v] == [
        "num_experts", "num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts"]
    assert (config["num_hidden_layers"], config["num_experts"]) == (4, 256)
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512}
    assert config["deployment_chips_per_layer"] == 2
    for key in ("column_grouping", "init_sigma", "rope_pairing",
                "float32_parts", "unread_keys"):
        assert config["assumed"][key]
    for key in ("intermediate_size", "max_position_embeddings"):
        assert key in config["assumed"]["unread_keys"]


def test_the_cut_keeps_the_rules_and_a_cut_width_is_refused(config, builder):
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    cut.check(entry, config, builder)
    assert builder.CUT == {"depth": "num_hidden_layers",
                           "experts_held": "num_experts"}
    assert builder.period(config) == 4 and builder.leading_dense(config) == 0
    for key, value in (("moe_intermediate_size", 256), ("head_dim", 128),
                       ("linear_key_head_dim", 64), ("num_experts_per_tok", 8),
                       ("vocab_size", 75968), ("partial_rotary_factor", 0.5)):
        with pytest.raises(cut.Refused, match=key) as refused:
            cut.check(entry, {**config, key: value}, builder)
        assert refused.value.rule == "family"
    # the router keeps every published expert
    with pytest.raises(cut.Refused, match="512") as refused:
        cut.check(entry, {**config, "published": {
            "num_hidden_layers": 48, "num_experts": 256}}, builder)
    assert refused.value.rule == "family"
    with pytest.raises(cut.Refused) as refused:  # no whole period
        cut.check(entry, {**config, "num_hidden_layers": 3}, builder)
    assert refused.value.rule == "family"
    with pytest.raises(cut.Refused) as refused:
        cut.check(entry, {**config, "num_experts": 4}, builder)
    assert refused.value.rule == "experts_floor"
    with pytest.raises(cut.Refused) as refused:
        cut.check({**entry, "reduced": ["num_hidden_layers", "vocab_size"]},
                  {**config, "reduced": ["num_hidden_layers", "vocab_size"]},
                  builder)
    assert refused.value.rule == "cut_key"


def test_the_counts_against_a_hand_count_at_the_published_widths(
        config, builder):
    expert = 3 * 2048 * 512
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048 + 256 * expert + 2 * 2048
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 2 * 256 + 4096 * 2048
    assert (gdn, attn) == (33_718_464, 27_263_488)
    assert builder.layer_params(config) == {
        "gdn_layer": gdn + ffn, "attn_layer": attn + ffn,
        "embedding_and_head": 2 * 151936 * 2048 + 2048}
    assert builder.param_count(config) == 3_988_778_048
    # a slot's state: S of 32 x 128 x 128 and 3 x 8192 kept inputs,
    # float32, in each of 3 layers; 2 KB of keys and values a token
    assert builder.slot_state_bytes(config) == 3 * (2_097_152 + 98_304)
    assert builder.kv_token_bytes(config) == 2048
    # 32 rows' 320 picks are expected to reach 46.8 % of the 256 held
    assert abs(builder.experts_reached(config, 32) / 256 - 0.468) < 0.001
    assert builder.experts_reached(config, 2048) > 255.99
    ops, nbytes = builder.decode_step_cost(config, 32, 9900.0)
    experts = 4 * builder.experts_reached(config, 32) * expert * 2
    assert abs(experts / 1e9 - 3.01) < 0.02
    assert abs(nbytes / 1e9 - 5.0) < 0.1  # the 5.0 GB reckoned by hand
    assert ops / 197e12 < nbytes / 819e9 / 5  # the bytes bound it
    _, chunk_bytes = builder.chunk_prefill_cost(config, 2048, 0)
    assert abs(chunk_bytes / 1e9 - 7.36) < 0.05  # every held expert once
    assert builder.picks_reading(config, {"moe_picks": 400}, 10) == 0.0
    assert builder.picks_reading(config, {"moe_picks": 440}, 10) == 0.1


def test_the_builder_and_cli_serve_build_the_one_engine_class(config, builder):
    from distributed_model_parallel_tpu.serving.engine import ServingEngine

    engine = builder.serving_engine(builder.rehearse(config))
    assert type(engine) is ServingEngine
    assert engine.family.name == "qwen3_next"
    program = builder.program_config(config)
    assert program["num_experts"] == 512 and program["experts_held"] == [0, 256]
    assert "reduced" not in program and program["torch_dtype"] == "bfloat16"
    serving = config["serving"]
    assert (serving["num_slots"], serving["max_len"], serving["page_size"],
            serving["num_pages"], serving["prefix_cache"]) == (
        32, 33024, 64, None, False)
    assert 1024 <= serving["prefill_chunk"] <= 4096
    assert serving["sizing_why"] and config["deployment"]
    for name in READINGS:
        assert name in config["tolerance"]
        assert config["tolerance"][name + "_why"]


def test_the_cell_reports_exactly_its_lists():
    cell = manifest.resolve_cell(manifest.load_manifest(), CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "serve_drain_state_moe"
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tok_s", "serve_tpot_p50_ms", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == PER_LAYER


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_every_listed_metric(
        capsys, records, trace):
    capsys.readouterr()
    rc = run.main(
        ["--workload", CELL, "--seed", "4400000007", "--seconds", "2",
         "--trace", str(trace), "--rehearsal"],
        t_process=time.perf_counter(),
    )
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert line["correct"] is True and line["failed"] == 0, info["notes"]
    assert line["attempted"] >= 3 and info["compiles_in_window"] == 0
    check = info["check"]
    assert check["ok"] and check["check_tokens"] == 2 * 256 + 173 + 48
    assert check["rows"] == 49 and check["rows_a_tie_went_the_other_way"] == 0
    assert set(check["readings"]) == set(check["limits"]) == set(READINGS)
    assert check["readings"]["serve_logits"] < 1e-5
    assert check["readings"]["recycled_slot"] == 0.0
    assert check["readings"]["moe_picks"] == 0.0
    paged = info["paged"]
    assert paged["state_pool_bytes"] > 0 and paged["latent_pool_bytes"] == 0
    assert 0 < paged["moe_picks_held"] < paged["moe_picks"]

    (cell, record), = records
    assert cell.config["hidden_size"] == 64  # the builder's toy widths
    listed = {x["name"] for x in (cell.per_layer if trace else cell.end_to_end)}
    if trace:
        # every listed metric that needs no device's peak table or
        # compiled-program line, which the CPU has not
        cpu_blind = {"decode_step_roofline", "prefill_chunk_roofline",
                     "serve_hbm_peak_gb"}
        assert listed - cpu_blind <= set(line["metrics"]) <= listed
        assert 0 < line["metrics"]["state_traffic_share"]["value"] < 100
    else:
        assert set(line["metrics"]) == listed


@pytest.fixture(scope="module")
def toy(config, builder):
    """(toy configuration, sizes, reference, weights) for the driver's
    comparisons, float32 throughout."""
    import jax

    toy = builder.rehearse(config)
    reference = manifest.load_module("reference", toy["reference"])
    params = builder.serving_engine(toy).init_params(jax.random.PRNGKey(11))
    return toy, builder.shape(toy), reference, params


def state_checks(toy):
    """The driver's comparisons on a FRESH engine, so that what a test
    has patched is what its steps are traced from."""
    config, sizes, reference, params = toy
    driver = manifest.load_module("driver", "serve_drain_state_moe")
    builder = manifest.load_module("builder", "qwen3_next")
    return driver.check_against_reference(
        builder.serving_engine(config), params, config, 7, sizes, reference)


def test_the_comparisons_pass_on_the_program_as_it_is(toy):
    check = state_checks(toy)
    assert check["ok"], check
    assert check["regret_by_layer"] == [0.0] * 4
    assert len(check["logit_err_decode"]) == 48
    assert len(set(check["check_slots"])) >= 2  # the probe moved slot


# The faults the comparisons are there for, each planted by a
# monkeypatch (`experiments/qwen3_next_compare.py --faults` plants the
# same ones at the published widths on the chip).


def _low(v):
    import jax
    import jax.numpy as jnp

    return jax.lax.reduce_precision(
        v.astype(jnp.float32), exponent_bits=8, mantissa_bits=7)


def bfloat16_delta_state(monkeypatch):
    """The delta rule's state rounded to bfloat16 after every chunk of
    the chunk program and every token of the decode step."""
    from distributed_model_parallel_tpu.models import qwen3_next
    from distributed_model_parallel_tpu.ops import delta_rule

    state_step, token_step = delta_rule._state_step, qwen3_next.gated_delta_rule_step

    def rounded_state_step(s0, terms):
        s, out = state_step(s0, terms)
        return _low(s), out

    def rounded_token_step(*args):
        out, s = token_step(*args)
        return out, _low(s)

    monkeypatch.setattr(delta_rule, "_state_step", rounded_state_step)
    monkeypatch.setattr(qwen3_next, "gated_delta_rule_step",
                        rounded_token_step)


def unrotated_key(monkeypatch):
    """The key is cached as the projection made it (the query is still
    rotated)."""
    from distributed_model_parallel_tpu.ops.latent_attention import (
        rotate_part,
    )
    from distributed_model_parallel_tpu.serving import decode

    def rotated(self, q, k_new, positions):
        return rotate_part(q, positions, self.rotary), k_new

    monkeypatch.setattr(decode._HeadsRecorder, "_rotated", rotated)


def bfloat16_rotation(monkeypatch):
    """Positions, angles, factors and products of the rotation rounded
    to bfloat16 (`reduce_precision`: converts the compiler may drop)."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.ops import latent_attention

    def rope(x, positions, theta):
        half = x.shape[-1] // 2
        inv = _low(theta ** (-jnp.arange(half, dtype=jnp.float32) / half))
        ang = _low(_low(positions)[..., None] * inv)
        if x.ndim == 4:
            ang = ang[:, :, None, :]
        cos, sin = _low(jnp.cos(ang)), _low(jnp.sin(ang))
        a, b = _low(x[..., :half]), _low(x[..., half:])
        return jnp.concatenate([
            _low(_low(a * cos) - _low(b * sin)),
            _low(_low(b * cos) + _low(a * sin))], -1)

    monkeypatch.setattr(latent_attention, "rope", rope)


def advancing_tail(monkeypatch):
    """A chunk's padded tail decays and writes the state: where the
    mixer gave it beta = 0 and g = 0, the op is handed 0.5 and -0.5."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import qwen3_next

    sound = qwen3_next.gated_delta_rule_from

    def advancing(q, k, v, g, beta, s0, **kw):
        pad = beta == 0
        return sound(q, k, v, jnp.where(pad[..., None], -0.5, g),
                     jnp.where(pad, 0.5, beta), s0, **kw)

    monkeypatch.setattr(qwen3_next, "gated_delta_rule_from", advancing)


def dropped_expert(monkeypatch):
    """One held expert's rows come back as zeros."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import moe

    part = moe.held_experts_part
    monkeypatch.setattr(
        moe, "held_experts_part",
        lambda w, flat, ids, weights, first, real=None: part(
            w, flat, ids, jnp.where(ids == 3, 0.0, weights), first, real))


def a_wrong_expert(monkeypatch):
    """The router takes, for the last of its experts, the one it finds
    least likely: the logits are those of a model that chose so (the
    reference, made to take the same experts, agrees), and only the
    choice itself is wrong."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import moe

    sound = moe.route_softmax

    def route(scores_in, router_w, bias, top_k, scale):
        ids, _ = sound(scores_in, router_w, bias, top_k, scale)
        probs = jax.nn.softmax(jnp.matmul(
            scores_in.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST), axis=-1)
        ids = ids.at[:, -1].set(jnp.argmin(probs, axis=-1))
        picked = jnp.take_along_axis(probs, ids, axis=-1)
        return ids, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)

    monkeypatch.setattr(moe, "route_softmax", route)


def unmasked_rows(monkeypatch):
    """The expert layers route, multiply and count the rows the mask
    calls not real: a chunk's padded tail, an inactive slot."""
    from distributed_model_parallel_tpu.models import moe

    part = moe.held_experts_part
    monkeypatch.setattr(
        moe, "held_experts_part",
        lambda w, flat, ids, weights, first, real=None: part(
            w, flat, ids, weights, first))


FAULTS = {f.__name__: f for f in (
    bfloat16_delta_state, unrotated_key, bfloat16_rotation, advancing_tail,
    dropped_expert, a_wrong_expert, unmasked_rows)}
# the reading each fault is seen by
SEEN_BY = {"bfloat16_delta_state": "delta_state", "unrotated_key": "kv_rows",
           "bfloat16_rotation": "kv_rows", "advancing_tail": "delta_state",
           "dropped_expert": "moe_rows", "a_wrong_expert": "router_regret",
           "unmasked_rows": "moe_picks"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_is_not_correct_by_its_reading(
        toy, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    check = state_checks(toy)
    assert not check["ok"]
    name = SEEN_BY[fault]
    assert check["readings"][name] > check["limits"][name], check["readings"]
    if fault in ("dropped_expert", "a_wrong_expert", "unmasked_rows"):
        # none of them moves the first layer's state: no expert layer
        # lies before it
        assert check["readings"]["delta_state"] <= check["limits"][
            "delta_state"]
    if fault == "a_wrong_expert":
        assert check["rows_a_tie_went_the_other_way"] == check["rows"]


def test_the_accepted_readers_read_the_cells_record(config, builder):
    """The serving readers the cell joins, on a canned record of this
    family: the rooflines through the builder's counts, the state's
    share of a decode step's traffic through its pool."""
    record = {
        "device": {"kind": "TPU v5 lite"},
        "device_trace": {
            "program_median_s": {"jit_chunk_prefill_step": 0.030,
                                 "jit_paged_decode_step": 0.010},
            "device0_busy_s": 1.6},
        "finished": [{"prompt_len": 9900, "n_tokens": 100},
                     {"prompt_len": 8100, "n_tokens": 60}],
        "paged": {"state_pool_bytes": 32 * builder.slot_state_bytes(config),
                  "prefill_positions_valid": 1400,
                  "prefill_positions_computed": 2048},
        "shape": builder.shape(config),
        "slots": 32, "decode_steps": 10, "step_occupancy_sum": 300,
        "prefill_chunk": 2048,
        "chunk_prefill_cost": functools.partial(
            builder.chunk_prefill_cost, config),
        "decode_step_cost": functools.partial(
            builder.decode_step_cost, config),
    }
    read = lambda name: manifest.load_module("per_layer", name).compute(record)
    live = (9950 + 8130) / 2
    _, nbytes = builder.decode_step_cost(config, 30.0, live)
    assert read("state_traffic_share") == pytest.approx(
        100 * 2 * 30 * builder.slot_state_bytes(config) / nbytes)
    assert 0 < read("decode_step_roofline") < 100
    assert 0 < read("prefill_chunk_roofline") < 100
    assert read("prefill_pad_share") == pytest.approx(100 * (1 - 1400 / 2048))
