"""The GLM-4.7-Flash configuration and its cell `glm47f_serve_longdoc`:
the file against the catalog row it comes from, the cut against the
rules, the builder's counts against a hand count at the published
widths, the cell's `--rehearsal` through `run.main` at the builder's
toy widths, the comparisons `serve_drain_latent` puts behind `correct`
(each seen to fail on the fault it is there for), and the new readers
on canned records."""

import functools
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import cut, manifest  # noqa: E402

CELL = "glm47f_serve_longdoc"
CONFIG = "glm-4.7-flash"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "GLM-4.7-Flash"
NEW_READERS = ("moe_experts_hit_share", "latent_traffic_share",
               "serve_moe_kernel_share")
READINGS = ("serve_logits", "shared_prefix", "router_regret", "latent_rows",
            "router_picks", "moe_picks")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def builder():
    return manifest.load_module("builder", "glm_moe")


def test_every_key_of_the_catalog_row_is_in_the_file_but_the_depth(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == ROW)
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if config.get(k) != v] == [
        "num_hidden_layers"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 7
    assert config["published"] == {"num_hidden_layers": 47}
    for key in ("rope_pairing", "cache_layout", "router", "float32_parts",
                "unread_keys"):
        assert config["assumed"][key]
    for key in ("num_nextn_predict_layers", "num_key_value_heads",
                "max_position_embeddings"):
        assert key in config["assumed"]["unread_keys"]
    assert "640" in config["assumed"]["cache_layout"]


def test_the_cut_keeps_the_rules_and_a_cut_width_is_refused(config, builder):
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    cut.check(entry, config, builder)
    assert builder.CUT == {"depth": "num_hidden_layers"}
    assert builder.period(config) == 1 and builder.leading_dense(config) == 1
    for key, value in (("moe_intermediate_size", 768), ("kv_lora_rank", 256),
                       ("n_routed_experts", 8), ("num_experts_per_tok", 2),
                       ("vocab_size", 19360), ("q_lora_rank", 384)):
        with pytest.raises(cut.Refused, match=key) as refused:
            cut.check(entry, {**config, key: value}, builder)
        assert refused.value.rule == "family"
    with pytest.raises(cut.Refused) as refused:  # the dense layer and three
        cut.check(entry, {**config, "num_hidden_layers": 4}, builder)
    assert refused.value.rule == "depth_floor"
    cut.check(entry, {**config, "num_hidden_layers": 5}, builder)
    with pytest.raises(cut.Refused) as refused:
        cut.check({**entry, "reduced": ["num_hidden_layers", "vocab_size"]},
                  {**config, "reduced": ["num_hidden_layers", "vocab_size"]},
                  builder)
    assert refused.value.rule == "cut_key"


def test_the_counts_against_a_hand_count_at_the_published_widths(
        config, builder):
    assert builder.param_count(config) == 4_530_936_960
    assert builder.layer_params(config) == {
        "dense_layer": 84_677_888, "expert_layer": 635_311_424,
        "embedding_and_head": 634_390_528}
    assert builder.latent_token_bytes(config) == 7 * 576 * 2 == 8064
    s = builder.shape(config)
    assert s["mixer_matmul"] == 21_757_952 and s["expert_matmul"] == 9_437_184
    assert (s["expert_layers"], s["experts"], s["picks"]) == (6, 64, 4)
    # 32 slots' 128 picks are expected to reach 55.5 of 64 experts
    assert abs(builder.experts_reached(config, 32) - 55.5) < 0.05
    assert builder.experts_reached(config, 1024) > 63.99
    # a full decode step at 4.5 k live positions: 7.4 GB of weights,
    # 1.16 GB of latent rows, 10.5 ms of HBM traffic on a v5e
    ops, nbytes = builder.decode_step_cost(config, 32, 4500.0)
    rows = 32 * 4500 * 8064
    weights = nbytes - rows - 32 * 154880 * 4
    assert rows == 1_161_216_000 and abs(weights / 1e9 - 7.46) < 0.02
    assert abs(nbytes / 819e9 - 10.5e-3) < 0.1e-3
    assert ops / 197e12 < nbytes / 819e9 / 5  # the bytes bound it
    attention = 32 * 2 * 7 * 20 * (2 * 512 + 64) * 4500
    assert ops > attention > 0.35 * ops
    # a chunk of 1024 positions at start 2048: every expert read once
    ops, nbytes = builder.chunk_prefill_cost(config, 1024, 2048)
    assert abs(nbytes / 1e9 - 8.46) < 0.02  # all but the embedding
    blocks = 7 * s["mixer_matmul"] + s["dense_matmul"] + 6 * (
        s["router_matmul"] + 5 * s["expert_matmul"])
    pairs = 1024 * 2048 + 1024 * 1025 / 2
    attend = 7 * (3072 * 512 * 20 * 448 + pairs * 20 * 512)
    assert ops == 2.0 * (1024 * blocks + 154880 * 2048 + attend)
    assert 5e-3 < 2.0 * 1024 * blocks / 197e12 < 6e-3  # the products alone
    assert builder.picks_reading(config, {"moe_picks": 240}, 10) == 0.0
    assert builder.picks_reading(config, {"moe_picks": 264}, 10) == 0.1


def test_the_builder_and_cli_serve_build_the_one_engine_class(config, builder):
    from distributed_model_parallel_tpu.serving.engine import ServingEngine

    engine = builder.serving_engine(builder.rehearse(config))
    assert type(engine) is ServingEngine
    assert engine.family.name == "glm4_moe_lite"
    serving = config["serving"]
    assert (serving["num_slots"], serving["max_len"], serving["page_size"],
            serving["prefill_chunk"], serving["prefix_cache"]) == (
        32, 16384, 64, 1024, True)
    assert serving["sizing_why"] and config["deployment"]
    assert builder.reference_args(config) == {"arch": {
        "heads": 20, "rank": 512, "nope": 192, "rope": 64, "dv": 256,
        "theta": 1e6, "eps": 1e-5, "top_k": 4, "routed_scale": 1.8}}
    assert builder.serving_widths(config) == {
        "weight_bytes": 2, "cache_bytes": 2}
    assert config["precision"]["latent_pages"] == "bfloat16"


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_every_listed_metric(
        capsys, records, trace):
    capsys.readouterr()
    rc = run.main(
        ["--workload", CELL, "--seed", "3600000007", "--seconds", "2",
         "--trace", str(trace), "--rehearsal"],
        t_process=time.perf_counter(),
    )
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert line["correct"] is True and line["failed"] == 0, info["notes"]
    assert line["attempted"] >= 5 and info["compiles_in_window"] == 0
    assert line["device"]["platform"] == "cpu"
    check = info["check"]
    assert check["ok"] and check["check_tokens"] == 2 * 256 + 173 + 48
    # every row is held, each alone, with the experts its step took
    assert check["rows"] == 2 * 49
    assert check["rows_a_tie_went_the_other_way"] == 0  # float32 both
    assert check["readings"]["router_regret"] == 0.0
    assert set(check["readings"]) == set(check["limits"]) == set(READINGS)
    assert check["logit_err_prefill"] < 1e-5 > max(check["logit_err_decode"])
    assert max(check["attached_logit_err"]) < 1e-5  # float32 against float32
    assert check["prefix_tokens_reused"] >= 2 * 256 + 173
    assert check["cow_copies"] >= 1 and len(check["check_slots"]) == 2
    assert check["readings"]["latent_rows"] < 1e-6
    assert check["readings"]["router_picks"] == 0.0
    assert check["readings"]["moe_picks"] == 0.0
    paged = info["paged"]
    assert paged["latent_pool_bytes"] > 0 and paged["state_pool_bytes"] == 0
    assert paged["moe_picks"] > 0 and paged["moe_experts_hit"] > 0
    assert info["prefix"]["hits"] >= 1

    (cell, record), = records
    assert cell.traffic["driver"] == "serve_drain_latent"
    assert cell.config["hidden_size"] == 64  # the builder's toy widths
    listed = {x["name"] for x in (cell.per_layer if trace else cell.end_to_end)}
    if trace:
        # every listed metric that needs no device's peak table,
        # compiled-program line or Mosaic kernel, which the CPU has not
        cpu_blind = {"decode_step_roofline", "prefill_chunk_roofline",
                     "serve_hbm_peak_gb", "serve_moe_kernel_share"}
        assert listed - cpu_blind <= set(line["metrics"]) <= listed
        assert 0 < line["metrics"]["moe_experts_hit_share"]["value"] <= 100
        assert 0 < line["metrics"]["latent_traffic_share"]["value"] < 100
        assert 0 < line["metrics"]["prefix_hit_share"]["value"] < 100
    else:
        assert set(line["metrics"]) == listed == {
            "serve_out_tok_s", "serve_tpot_p50_ms", "setup_s"}


@pytest.fixture(scope="module")
def toy(config, builder):
    """(toy configuration, sizes, reference, weights) for the driver's
    comparisons, float32 throughout."""
    import jax

    toy = builder.rehearse(config)
    reference = manifest.load_module("reference", toy["reference"])
    params = builder.serving_engine(toy).init_params(jax.random.PRNGKey(11))
    return toy, builder.shape(toy), reference, params


def latent_checks(toy):
    """The driver's comparisons on a FRESH engine, so that what a test
    has patched is what its steps are traced from."""
    config, sizes, reference, params = toy
    driver = manifest.load_module("driver", "serve_drain_latent")
    builder = manifest.load_module("builder", "glm_moe")
    return driver.check_against_reference(
        builder.serving_engine(config), params, config, 7, sizes, reference)


def test_the_comparisons_pass_on_the_program_as_it_is(toy):
    check = latent_checks(toy)
    assert check["ok"], check
    assert check["rows"] == 98 and check["regret_by_layer"] == [0.0, 0.0]
    assert len(check["logit_err_decode"]) == 48 == len(
        check["attached_logit_err"]) - 1


# The faults the comparisons are there for, each planted by a
# monkeypatch (`experiments/glm_compare.py --faults` plants the same
# ones at the published widths on the chip).


def _low(v):
    import jax
    import jax.numpy as jnp

    return jax.lax.reduce_precision(
        v.astype(jnp.float32), exponent_bits=8, mantissa_bits=7)


def bfloat16_router(monkeypatch):
    """The router's scores from a product of bfloat16 operands."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import moe

    def route(scores_in, router_w, bias, top_k, scale):
        scores = jax.nn.sigmoid(jnp.matmul(
            scores_in.astype(jnp.bfloat16), router_w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32))
        _, ids = jax.lax.top_k(scores + bias, top_k)
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        return ids, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)

    monkeypatch.setattr(moe, "route", route)


def bfloat16_rotation(monkeypatch):
    """Positions, angles, factors and products of the rotation rounded
    to bfloat16 (`reduce_precision`: converts the compiler may drop)."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.serving import decode

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

    monkeypatch.setattr(decode, "rope", rope)


def unrotated_key(monkeypatch):
    """The shared rotary key is cached as the projection made it."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.serving import decode

    def rows(self, c, k_rope, pos, dims, pool):
        b, t, _ = c.shape
        return jnp.concatenate([
            c, k_rope.astype(c.dtype),
            jnp.zeros((b, t, pool.shape[-1] - dims.row), c.dtype),
        ], -1).astype(pool.dtype)

    monkeypatch.setattr(decode._LatentRecorder, "_rows", rows)


def unmasked_tail(monkeypatch):
    """The expert layers route, multiply and count the rows the mask
    calls not real: a chunk's padded tail, an inactive slot."""
    from distributed_model_parallel_tpu.models import moe

    part = moe.held_experts_part
    monkeypatch.setattr(
        moe, "held_experts_part",
        lambda w, flat, ids, weights, first, real=None: part(
            w, flat, ids, weights, first))


def dropped_expert(monkeypatch):
    """One routed expert's rows come back as zeros (its group of the
    sorted buffer lost): a fault that spoils only the rows one of whose
    picks is that expert, a minority."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import moe

    part = moe.held_experts_part
    monkeypatch.setattr(
        moe, "held_experts_part",
        lambda w, flat, ids, weights, first, real=None: part(
            w, flat, ids, jnp.where(ids == 3, 0.0, weights), first, real))


def a_wrong_expert(monkeypatch):
    """The router takes, for the last of its experts, the one it scores
    lowest: the logits are those of a model that chose so (the
    reference, made to take the same experts, agrees), and only the
    choice itself is wrong."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import moe

    sound = moe.route

    def route(scores_in, router_w, bias, top_k, scale):
        ids, _ = sound(scores_in, router_w, bias, top_k, scale)
        scores = jax.nn.sigmoid(jnp.matmul(
            scores_in.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        ids = ids.at[:, -1].set(jnp.argmin(scores + bias, axis=-1))
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        return ids, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)

    monkeypatch.setattr(moe, "route", route)


FAULTS = {f.__name__: f for f in (
    bfloat16_router, bfloat16_rotation, unrotated_key, unmasked_tail,
    dropped_expert, a_wrong_expert)}
# the reading each fault is seen by (on the chip by this one; at toy
# widths in float32 an unrotated key moves the logits too)
SEEN_BY = {"bfloat16_router": "router_picks",
           "bfloat16_rotation": "latent_rows",
           "unrotated_key": "latent_rows", "unmasked_tail": "moe_picks",
           "dropped_expert": "serve_logits",
           "a_wrong_expert": "router_regret"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_each_planted_fault_is_not_correct_by_its_reading(
        toy, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    check = latent_checks(toy)
    assert not check["ok"]
    name = SEEN_BY[fault]
    assert check["readings"][name] > check["limits"][name], check["readings"]
    if fault == "unrotated_key":
        assert check["readings"]["serve_logits"] > check["limits"][
            "serve_logits"]
    if fault in ("bfloat16_router", "unmasked_tail", "dropped_expert"):
        # none of them moves the rows the pool holds
        assert check["readings"]["latent_rows"] <= check["limits"][
            "latent_rows"]
    if fault == "a_wrong_expert":
        # in every row and layer, by a tenth and more
        assert check["rows_a_tie_went_the_other_way"] == check["rows"]
        assert min(check["regret_by_layer"]) > 0.1
    if fault == "dropped_expert":
        # (on the chip it spoils a third of the rows, PERF.md section 6;
        # against float32's limits here every row feels the prompt's)
        assert check["readings"]["shared_prefix"] > check["limits"][
            "shared_prefix"]


def canned(builder, config, **over):
    record = {
        "device": {"kind": "TPU v5 lite"},
        "device_trace": {
            "program_median_s": {"jit_chunk_prefill_step": 0.030,
                                 "jit_paged_decode_step": 0.025},
            "kernel_seconds": {"gmm": 0.12, "fusion.1": 0.5},
            "device0_busy_s": 1.6},
        "finished": [{"prompt_len": 5100, "n_tokens": 100},
                     {"prompt_len": 3300, "n_tokens": 60}],
        "paged": {"latent_pool_bytes": 4096 * 64 * 8960, "num_pages": 4096,
                  "page_size": 64, "moe_experts_hit": 10 * 6 * 52,
                  "prefill_positions_valid": 1400,
                  "prefill_positions_computed": 2048},
        "shape": builder.shape(config),
        "slots": 32, "decode_steps": 10, "step_occupancy_sum": 250,
        "prefill_chunk": 1024,
        "chunk_prefill_cost": functools.partial(
            builder.chunk_prefill_cost, config),
        "decode_step_cost": functools.partial(
            builder.decode_step_cost, config),
    }
    record.update(over)
    return record


def test_the_new_readers_on_a_canned_record(config, builder):
    record = canned(builder, config)
    read = lambda name: manifest.load_module("per_layer", name).compute(record)
    assert read("moe_experts_hit_share") == pytest.approx(100 * 52 / 64)
    live = (5150 + 3330) / 2
    _, nbytes = builder.decode_step_cost(config, 25.0, live)
    assert read("latent_traffic_share") == pytest.approx(
        100 * 25 * live * 8064 / nbytes)
    assert 8 < read("latent_traffic_share") < 14
    assert read("serve_moe_kernel_share") == pytest.approx(100 * 0.12 / 1.6)
    # the accepted rooflines read the same record through the builder
    assert 30 < read("decode_step_roofline") < 50
    assert 25 < read("prefill_chunk_roofline") < 50


@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_read_nothing_where_nothing_is(config, builder, name):
    """A program without the counters, a family without experts, a
    trace without the kernels: nothing, and no error."""
    reader = manifest.load_module("per_layer", name)
    bare = canned(builder, config, paged={"page_size": 16, "num_pages": 8},
                  device_trace=None, shape={"builder": "gpt",
                                            "vocab_size": 50257})
    assert reader.compute(bare) is None
    assert reader.compute({**bare, "paged": None}) is None
    assert reader.compute({**bare, "device_trace": {
        "kernel_seconds": {}, "device0_busy_s": 1.0}}) is None


def test_the_cells_lists_are_the_issues(config):
    m = manifest.load_manifest()
    cell = manifest.resolve_cell(m, CELL)
    assert cell.chips == 1 and cell.traffic_name == "longdoc_qa_drain"
    assert {x["name"] for x in cell.end_to_end} == {
        "serve_out_tok_s", "serve_tpot_p50_ms", "setup_s"}
    assert {x["name"] for x in cell.per_layer} == {
        "sched_slot_occupancy", "kv_pages_peak_share", "prefix_hit_share",
        "serve_host_share", "decode_step_p50_ms", "decode_step_roofline",
        "prefill_chunk_roofline", "prefill_pad_share", "serve_hbm_peak_gb",
        "serve_device_idle_share", *NEW_READERS}
    for entry in m["per_layer"]:
        if entry["name"] in NEW_READERS:
            assert entry["workloads"] == [CELL]
    requests = cell.traffic["requests"]
    assert requests["shape_seed"] == 47
    assert requests["prefix"] == {"dist": "lognormal", "median": 4096,
                                  "sigma": 0.6, "min": 1024, "max": 12288}
    assert requests["uses"] == {"dist": "uniform", "min": 3, "max": 5}
    assert requests["gap"] == {"dist": "geometric", "mean": 12}
    assert requests["suffix"] == {"dist": "uniform", "min": 32, "max": 256}
    assert requests["output"] == {"dist": "lognormal", "median": 96,
                                  "sigma": 0.5, "min": 32, "max": 256}
    assert cell.traffic["warmup_max_new_tokens"] == 8
    assert cell.traffic["trace_slice_s"] == 2.0
    rate = cell.params["drain_requests_per_s"]
    assert rate >= 2.5 and round(rate * m["run_seconds"]) >= 105
    assert cell.params["drain_requests_per_s_why"]
    for name in READINGS:
        assert config["tolerance"][name] >= 0
        assert config["tolerance"][name + "_why"]
    assert "serve_logits_flipped" not in config["tolerance"]
    # what the mix is: the first requests of the fixed list
    generator = manifest.load_module("generator", "sessions")
    stream = generator.generate(
        requests, vocab_size=154880, max_len=16384, seed=1, n=150)
    sizes = np.asarray([g["prompt"].size for g in stream])
    assert 1024 + 32 <= sizes.min() and sizes.max() <= 12288 + 256
    assert 3500 < np.median(sizes) < 6000
    assert all(32 <= g["max_new_tokens"] <= 256 for g in stream)
