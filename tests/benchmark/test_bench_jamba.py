"""The Jamba configuration and its cell `jamba2_serve_docs`: the file
against the catalog row it comes from, the builder's counts against a
hand count at the published widths, the cell's `--rehearsal` through
`run.main` at the builder's toy widths, the three comparisons
`serve_drain_state` adds behind `correct` (each seen to fail on the
fault it is there for), and the new readers on canned records."""

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

CELL = "jamba2_serve_docs"
CONFIG = "jamba2-3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "AI21-Jamba2-3B"
NEW_READERS = ("prefill_chunk_roofline", "prefill_pad_share",
               "state_traffic_share")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def builder():
    return manifest.load_module("builder", "jamba")


def test_every_key_of_the_catalog_row_is_in_the_file_with_its_value(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == ROW)
    assert config["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if config.get(k) != v] == []
    assert config["reduced"] == []
    for key in ("layer_order", "float32_parts", "unread_keys"):
        assert config["assumed"][key]
    for key in ("use_mamba_kernels", "num_logits_to_keep",
                "max_position_embeddings"):
        assert key in config["assumed"]["unread_keys"]


def test_the_configuration_keeps_the_rules_and_a_cut_width_is_refused(
        config, builder):
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    cut.check(entry, config, builder)
    assert builder.layers(config).count("ssm") == 26
    assert [i for i, k in enumerate(builder.layers(config)) if k == "attn"] \
        == [7, 21]
    with pytest.raises(cut.Refused, match="mamba_dt_rank") as refused:
        cut.check(entry, {**config, "mamba_dt_rank": 80}, builder)
    assert refused.value.rule == "family"
    with pytest.raises(cut.Refused) as refused:
        cut.check({**entry, "reduced": ["num_hidden_layers"]},
                  {**config, "reduced": ["num_hidden_layers"]}, builder)
    assert refused.value.rule == "cut_key"  # this builder lets nothing be cut


def test_the_counts_against_a_hand_count_at_the_published_widths(
        config, builder):
    assert builder.param_count(config) == 3_029_337_472
    assert builder.slot_state_bytes(config) == 26 * 358_400 == 9_318_400
    s = builder.shape(config)
    assert s["ssm_matmul"] + s["ssm_other"] == 41_241_792
    assert s["mlp_matmul"] == 62_914_560 and s["attn_matmul"] == 13_762_560
    # a decode step of 32 slots with empty caches: the weights once and
    # the state pool in and out, 8.1 ms of HBM traffic on a v5e
    ops, nbytes = builder.decode_step_cost(config, 32, 0.0)
    state = 2 * 32 * 9_318_400
    assert nbytes == 3_029_337_472 * 2 + state + 32 * 65536 * 4
    assert abs(nbytes / 819e9 - 8.1e-3) < 0.1e-3
    # a chunk of 512 positions from the start: 14.9 ms of products at
    # 197 TFLOP/s with the head on one row (15.7 with it on all 512)
    ops, nbytes = builder.chunk_prefill_cost(config, 512, 0)
    blocks = 26 * (s["ssm_matmul"] + s["mlp_matmul"]) + 2 * (
        s["attn_matmul"] + s["mlp_matmul"])
    attention = 4 * 2 * 2560 * (512 * 513 / 2)
    scan = 7 * 512 * 26 * 5120 * 16
    assert ops == 2.0 * 512 * blocks + 2.0 * 65536 * 2560 + attention + scan
    assert abs(ops / 197e12 - 14.9e-3) < 0.1e-3
    assert abs((ops + 2.0 * 65536 * 2560 * 511) / 197e12 - 15.7e-3) < 0.1e-3
    assert 2.0 * 65536 * 2560 * 512 / ops > 0.05  # what 512 head rows would add
    later, _ = builder.chunk_prefill_cost(config, 512, 2048)
    assert later - ops == 4 * 2 * 2560 * 512 * 2048
    assert builder.train_flops_per_token(config, 1024) > 6 * blocks


def test_the_builder_and_cli_serve_build_the_one_engine_class(config, builder):
    from distributed_model_parallel_tpu.serving.engine import ServingEngine

    toy = builder.rehearse(config)
    engine = builder.serving_engine(toy)
    assert type(engine) is ServingEngine
    assert engine.family.name == "jamba"
    assert engine.prefill_chunk == config["serving"]["prefill_chunk"]
    assert config["serving"]["prefix_cache"] is False
    assert config["serving"]["num_slots"] == 32
    assert config["serving"]["max_len"] == 8192
    assert builder.reference_args(config) == {
        "num_heads": 20, "num_kv_heads": 1, "eps": 1e-6}
    assert builder.serving_widths(config) == {
        "weight_bytes": 2, "cache_bytes": 2, "state_bytes": 4}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_and_prints_every_listed_metric(
        capsys, records, trace):
    capsys.readouterr()
    rc = run.main(
        ["--workload", CELL, "--seed", "3400000007", "--seconds", "2",
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
    assert check["ok"] and check["state_tokens"] == 2 * 512 + 173 + 16
    assert check["logit_err_prefill"] < 1e-4 > max(check["logit_err_decode"])
    assert len(check["state_slots"]) == 3  # probe, long prompt, probe again
    assert max(check["carried_logit_err"]) < 1e-4   # float32 against float32
    assert max(check["recycled_logit_diff"]) == 0.0
    assert set(check["state_readings"]) == {"ssm_state", "ssm_recurrence"}
    assert max(check["state_readings"].values()) < 1e-5
    paged = info["paged"]
    assert paged["state_pool_bytes"] > 0 and info["prefix"] is None
    assert paged["state_resets"] == line["attempted"]
    assert 0 < paged["prefill_positions_valid"] < paged[
        "prefill_positions_computed"]

    (cell, record), = records
    assert cell.traffic["driver"] == "serve_drain_state"
    assert cell.config["hidden_size"] == 64  # the builder's toy widths
    listed = {x["name"] for x in (cell.per_layer if trace else cell.end_to_end)}
    if trace:
        # every listed metric that needs no device's peak table or
        # compiled-program line, which the CPU has not
        cpu_blind = {"decode_step_roofline", "prefill_chunk_roofline",
                     "serve_hbm_peak_gb"}
        assert listed - cpu_blind <= set(line["metrics"]) <= listed
        assert 0 < line["metrics"]["prefill_pad_share"]["value"] < 60
        assert 0 < line["metrics"]["state_traffic_share"]["value"] < 100
    else:
        # not serve_tpot_p90_ms: in this saturated drain its six runs
        # spread over half its bound (PERF.md sections 6 and 7)
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


def state_checks(toy):
    """The driver's comparisons on a FRESH engine, so that what a test
    has patched is what its steps are traced from."""
    config, sizes, reference, params = toy
    driver = manifest.load_module("driver", "serve_drain_state")
    builder = manifest.load_module("builder", "jamba")
    return driver.check_against_reference(
        builder.serving_engine(config), params, config, 7, sizes, reference)


def test_the_three_comparisons_pass_on_the_program_as_it_is(toy):
    check = state_checks(toy)
    assert check["ok"], check
    assert max(check["recycled_logit_diff"]) == 0.0


# The faults the comparisons are there for, each planted by a
# monkeypatch (`experiments/jamba_compare.py --faults` plants the same
# ones at the published widths on the chip).


def bfloat16_pool(monkeypatch):
    """The pool declares its state bfloat16."""
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.models import jamba

    shapes = jamba.JambaConfig.state_shapes
    monkeypatch.setattr(
        jamba.JambaConfig, "state_shapes",
        lambda self: {**shapes(self), "h": (shapes(self)["h"][0], jnp.bfloat16)})


def bfloat16_step(monkeypatch):
    """The pool stays float32; the recurrence's one-position step, which
    the chunk program and the decode step both run, rounds its step
    sizes, its factors and the state it hands on to bfloat16
    (`reduce_precision`: a pair of converts the compiler may drop)."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.ops import ssm_scan

    low = lambda v: jax.lax.reduce_precision(
        v.astype(jnp.float32), exponent_bits=8, mantissa_bits=7)

    def step(x, delta, a, b, c, h):
        f32 = jnp.float32
        delta = low(delta)
        factor = low(jnp.exp(delta[:, None, :] * a.astype(f32)[None]))
        push = (delta * x.astype(f32))[:, None, :] * b.astype(f32)[:, :, None]
        new = low(factor * h.astype(f32) + push)
        y = jnp.sum(new * c.astype(f32)[:, :, None], axis=1)
        return y, new.astype(h.dtype)

    monkeypatch.setattr(ssm_scan, "selective_step", step)


def skipped_reset(monkeypatch):
    """Every chunk resumes what its slot holds, a prompt's first too."""
    from distributed_model_parallel_tpu.serving import decode, engine

    class NoReset(decode.SlotStateChunk):
        def read(self, arrays):
            self.start = 1
            return super().read(arrays)

    monkeypatch.setattr(engine, "SlotStateChunk", NoReset)


def unmasked_tail(monkeypatch):
    """A chunk's padded tail advances the state."""
    from distributed_model_parallel_tpu.models import jamba

    scan = jamba.selective_scan
    monkeypatch.setattr(
        jamba, "selective_scan",
        lambda x, delta, a, b, c, h0, valid: scan(x, delta, a, b, c, h0))


FAULTS = {f.__name__: f for f in (
    bfloat16_pool, bfloat16_step, skipped_reset, unmasked_tail)}


@pytest.mark.parametrize("plant", [bfloat16_pool, bfloat16_step])
def test_a_recurrence_in_bfloat16_is_not_correct(toy, monkeypatch, plant):
    plant(monkeypatch)
    check = state_checks(toy)
    assert not check["ok"]
    # both see it here; on the chip the first layer's state alone does
    # (PERF.md section 6)
    for name, value in check["state_readings"].items():
        assert value > check["state_limits"][name], name


def test_a_skipped_reset_is_not_correct(toy, monkeypatch):
    skipped_reset(monkeypatch)
    check = state_checks(toy)
    assert not check["ok"]
    assert max(check["recycled_logit_diff"]) > check["recycled_tol"]


def test_an_unmasked_tail_is_not_correct(toy, monkeypatch):
    unmasked_tail(monkeypatch)
    check = state_checks(toy)
    assert not check["ok"]
    assert check["state_readings"]["ssm_state"] > check["state_limits"]["ssm_state"]
    assert max(check["carried_logit_err"][1:]) > toy[0]["tolerance"][
        "serve_logits"]


def canned(builder, config, **over):
    import functools

    record = {
        "device": {"kind": "TPU v5 lite"},
        "device_trace": {"program_median_s": {
            "jit_chunk_prefill_step": 0.040, "jit_paged_decode_step": 0.016}},
        "finished": [{"prompt_len": 1100, "n_tokens": 60},
                     {"prompt_len": 300, "n_tokens": 20}],
        "paged": {"state_pool_bytes": 32 * 9_318_400,
                  "prefill_positions_valid": 1400,
                  "prefill_positions_computed": 2048},
        "slots": 32, "decode_steps": 10, "step_occupancy_sum": 200,
        "prefill_chunk": 512,
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
    # chunks begin at 0, 512, 1024 and at 0: mean 384
    ops, _ = builder.chunk_prefill_cost(config, 512, 384.0)
    assert read("prefill_chunk_roofline") == pytest.approx(
        100 * (ops / 197e12) / 0.040)
    assert 30 < read("prefill_chunk_roofline") < 50
    assert read("prefill_pad_share") == pytest.approx(100 * (1 - 1400 / 2048))
    _, nbytes = builder.decode_step_cost(config, 20.0, (1130 + 310) / 2)
    assert read("state_traffic_share") == pytest.approx(
        100 * 2 * 20 * 9_318_400 / nbytes)
    assert 5 < read("state_traffic_share") < 7
    # the accepted roofline reads the same record through the builder
    assert 40 < read("decode_step_roofline") < 60


@pytest.mark.parametrize("name", NEW_READERS)
def test_the_new_readers_read_nothing_where_nothing_is(config, builder, name):
    """A program without the counters, a driver without the counts, a
    trace without the program: nothing, and no error."""
    reader = manifest.load_module("per_layer", name)
    bare = canned(builder, config, paged={"page_size": 16}, device_trace=None)
    bare.pop("chunk_prefill_cost")
    bare.pop("prefill_chunk")
    assert reader.compute(bare) is None
    assert reader.compute({**bare, "paged": None}) is None


def test_the_cells_lists_are_the_issues(config):
    m = manifest.load_manifest()
    cell = manifest.resolve_cell(m, CELL)
    assert cell.chips == 1 and cell.traffic_name == "doc_read_drain"
    # ISSUE 34's list but for serve_prefill_share, which moves the
    # serve_tpot_p90_ms the cell does not report
    assert {x["name"] for x in cell.per_layer} == {
        "sched_slot_occupancy", "kv_pages_peak_share", "serve_host_share",
        "decode_step_p50_ms", "decode_step_roofline",
        "serve_hbm_peak_gb", "serve_device_idle_share", *NEW_READERS}
    requests = cell.traffic["requests"]
    assert requests["shape_seed"] == 41 and requests["prefix"] is None
    assert requests["suffix"] == {"dist": "lognormal", "median": 1024,
                                  "sigma": 0.7, "min": 256, "max": 4096}
    assert requests["output"] == {"dist": "lognormal", "median": 64,
                                  "sigma": 0.5, "min": 16, "max": 192}
    rate = cell.params["drain_requests_per_s"]
    assert rate >= 3.5 and round(rate * m["run_seconds"]) >= 105
    for name in ("serve_logits", "recycled_slot", "ssm_state",
                 "ssm_recurrence"):
        assert config["tolerance"][name] > 0
        assert config["tolerance"][name + "_why"]
