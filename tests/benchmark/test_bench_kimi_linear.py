"""The Kimi-Linear configuration and its cell `kimilin_train_8k`: the
cell's `--rehearsal` through `run.main` at the builder's toy widths,
the builder's counts against a hand count at the published widths, the
configuration against the catalog row it comes from, and the new
kernels' readers on canned records."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import cut, kernels, manifest  # noqa: E402

CELL = "kimilin_train_8k"
CONFIG = "kimi-linear-48b-a3b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
ROW = "Kimi-Linear-48B-A3B-Instruct"


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def builder():
    return manifest.load_module("builder", "kimi_linear")


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses_with_every_kind_of_layer(capsys, records, trace):
    from distributed_model_parallel_tpu.cli import lm
    from distributed_model_parallel_tpu.training.trainer import Trainer

    capsys.readouterr()
    rc = run.main(
        ["--workload", CELL, "--seed", "2900000007", "--seconds", "0.5",
         "--trace", str(trace), "--rehearsal"],
        t_process=time.perf_counter(),
    )
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert line["correct"] is True and line["failed"] == 0, info["notes"]
    assert line["attempted"] >= 4 and info["compiles_in_window"] == 0
    assert line["device"]["platform"] == "cpu"
    assert lm.Trainer is Trainer  # the substitute is gone again
    check = info["check"]
    assert abs(check["step0_loss"] - check["reference_loss"]) < 1e-3
    assert check["epoch_losses"][-1] < check["epoch_losses"][0]
    # the stated precision, held by the cell's own driver: float32
    # against float32 here, far under limits set on the chip
    assert set(check["precision"]) == {"kda_recurrence", "router_picks"}
    for name, value in check["precision"].items():
        assert value <= check["precision_limits"][name] / 100, name
        assert check["precision_limits"][name] == cell_tolerance()[name]

    (cell, record), = records
    assert cell.config["hidden_size"] == 64  # the builder's toy widths
    assert record["shape"]["kinds"] == ["kda", "kda", "kda", "mla", "kda"]
    assert record["shape"]["leading_dense"] == 1
    assert (record["shape"]["experts_held"],
            record["shape"]["router_experts"]) == (4, 16)
    listed = {x["name"] for x in (cell.per_layer if trace else cell.end_to_end)}
    assert set(line["metrics"]) <= listed
    if trace:
        assert "train_data_share" in line["metrics"]
        # the CPU runs no Mosaic kernel: the kernels' readers read nothing
        assert not {"mla_kernel_share", "mla_kernel_roofline",
                    "moe_kernel_share", "moe_kernel_roofline"} & set(
                        line["metrics"])
    else:
        assert set(line["metrics"]) == {"train_tok_s", "setup_s"}


def cell_tolerance():
    with open(os.path.join(ROOT, "benchmark", "configs", CONFIG + ".json")) as f:
        return json.load(f)["tolerance"]


def test_a_run_in_the_next_precision_down_is_not_correct(
        capsys, monkeypatch):
    """The control through the harness's own comparison: with the
    reference in bfloat16 (state after every token, the scores'
    product) in the program's place, the same cell's run comes out
    `correct: false`, by the limit it passes and not by the loss."""
    load = manifest.load_module

    def lowered(kind, name, root=None):
        module = load(kind, name, root)
        if kind == "builder":
            sound = module.precision_readings
            module.precision_readings = (
                lambda *a, **kw: sound(*a, **kw, control=True))
        return module

    monkeypatch.setattr(manifest, "load_module", lowered)
    capsys.readouterr()
    rc = run.main(
        ["--workload", CELL, "--seed", "2900000008", "--seconds", "0.1",
         "--trace", "0", "--rehearsal"], t_process=time.perf_counter())
    out = capsys.readouterr()
    lines = out.out.strip().splitlines()
    line, info = json.loads(lines[-1]), json.loads(lines[-2])["info"]
    assert rc == 0 and line["correct"] is False
    check = info["check"]
    assert abs(check["step0_loss"] - check["reference_loss"]) < 1e-3
    assert check["precision"]["kda_recurrence"] > cell_tolerance()[
        "kda_recurrence"]
    assert any("kda_recurrence" in note for note in info["notes"])
    assert "NOT CORRECT: kda_recurrence" in out.err


@pytest.mark.parametrize("seed", [0, 1])
def test_the_limits_lie_between_the_program_and_the_control(
        config, builder, seed):
    """At the rehearsal's widths on the CPU (the chip's readings are in
    the configuration file beside each limit): the program two orders
    under the limit of the recurrence, the control over it; the
    program's router picks the reference's experts row for row."""
    import jax

    from distributed_model_parallel_tpu.models import kimi_linear as kl

    toy = builder.rehearse(config)
    reference = manifest.load_module("reference", toy["reference"])
    model = kl.kimi_linear_lm(kl.config_from_dict(builder.program_config(toy)))
    params, _ = jax.jit(model.init)(jax.random.PRNGKey(seed))
    ids = jax.random.randint(
        jax.random.PRNGKey(seed + 10), (8, 64), 0, toy["vocab_size"])
    sound = builder.precision_readings(toy, reference, params, ids)
    control = builder.precision_readings(
        toy, reference, params, ids, control=True)
    limit = config["tolerance"]
    assert sound["kda_recurrence"] < limit["kda_recurrence"] / 100
    assert control["kda_recurrence"] > 2 * limit["kda_recurrence"]
    assert sound["router_picks"] == 0 <= control["router_picks"]


def test_the_program_reads_the_cut_resolved_and_no_benchmark_key(
        config, builder):
    from distributed_model_parallel_tpu.models import kimi_linear as kl

    resolved = builder.program_config(config)
    assert resolved["num_experts"] == 256            # the router's width
    assert resolved["experts_held"] == [0, 8]
    assert resolved["vocab_size"] == 20480
    assert not set(resolved) & set(builder.BENCHMARK_KEYS)
    catalog_keys = set(config) - set(builder.BENCHMARK_KEYS)
    assert set(resolved) == catalog_keys | {"experts_held"}
    cfg = kl.config_from_dict(resolved)
    assert (cfg.num_experts, cfg.experts_held) == (256, (0, 8))
    # the program takes its keys alone: the cut's schema means nothing
    # to it (the file as it lies is an 8-expert model to the program)
    assert kl.config_from_dict(config).num_experts == 8


def test_the_configuration_keeps_the_rules_of_the_cut(config, builder):
    entry = next(c for c in manifest.load_manifest()["configs"]
                 if c["name"] == CONFIG)
    cut.check(entry, config, builder)
    assert builder.period(config) == 4 and builder.leading_dense(config) == 1
    assert builder.layers(config) == ["kda", "kda", "kda", "mla", "kda"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    # a width that differs is the family's to refuse
    with pytest.raises(cut.Refused) as refused:
        cut.check(entry, {**config, "moe_intermediate_size": 512}, builder)
    assert refused.value.rule == "family"
    with pytest.raises(cut.Refused) as refused:
        cut.check(entry, {**config, "num_hidden_layers": 4}, builder)
    assert refused.value.rule == "depth_floor"


def test_every_key_of_the_catalog_row_is_in_the_file_with_its_value(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == ROW)
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"])
    for key in config["reduced"]:
        assert config["published"][key] == row["config"][key]
        assert 0 < config[key] < row["config"][key]


def test_the_counts_against_a_hand_count_at_the_published_widths(
        config, builder):
    """One token's forward pass at 8,192 tokens, term by term."""
    d, width, heads = 2304, 32 * 128, 32
    by_hand = {
        # q, k, v, out; the decay's and the gate's low-rank pairs; beta
        "kda_projections": 4 * 2 * (
            4 * d * width + 2 * (d * 128 + 128 * width) + d * heads),
        # per head: A 63 x 128, B 65 x 128, the solve 63 x 256, three
        # products with the 128 x 128 state, B U 65 x 128
        "kda_delta_rule": 4 * heads * (
            63 * 128 + 65 * 128 + 63 * 256 + 6 * 128 * 128 + 65 * 128),
        "mla_projections": 2 * (
            d * heads * 192 + d * 576 + 512 * heads * 256 + heads * 128 * d),
        # (8192 + 1) / 2 keys a query, 2 x (192 + 128) a pair and head
        "mla_attention": heads * 2 * 320 * 8193 / 2,
        "dense_ffn": 2 * 3 * d * 9216,
        "shared_experts": 4 * 2 * 3 * d * 1024,
        "router": 4 * 2 * d * 256,
        # 8 picks a token, 8 of 256 experts held: a quarter of a row
        "held_experts": 4 * 0.25 * 2 * 3 * d * 1024,
        "head": 2 * d * 20480,
    }
    assert by_hand["kda_projections"] == 315_686_912
    assert by_hand["kda_delta_rule"] == 17_809_408
    terms = builder.forward_terms(config, 8192)
    assert terms == pytest.approx(by_hand, rel=1e-12)
    forward = sum(terms.values())
    assert forward == pytest.approx(772_892_672)
    assert 0.77e9 < forward < 0.78e9
    assert builder.train_flops_per_token(config, 8192) == 3 * forward
    # the mixers are most of the pass at 8k
    mixers = sum(v for k, v in terms.items() if k[:3] in ("kda", "mla"))
    assert mixers / forward > 0.6


def test_the_parameter_count_of_the_cut_as_the_program_builds_it(
        config, builder):
    """The cut's arithmetic on the built tree: 602 M parameters, 9.64 GB
    at 16 bytes each."""
    import jax

    from distributed_model_parallel_tpu.models import kimi_linear as kl

    model = kl.kimi_linear_lm(
        kl.config_from_dict(builder.program_config(config)))
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    size = lambda tree: sum(
        int(x.size) for x in jax.tree_util.tree_leaves(tree))
    blocks = [size(params["blocks"][str(i)]) for i in range(5)]
    # a KDA mixer: its matrices, three 4-tap convolutions, dt_bias, A,
    # the output norm; a latent mixer: its matrices and the kv norm
    kda = 39_460_864 + 3 * 4 * 4096 + 4096 + 32 + 128
    mla = 29_114_368 + 512
    experts = 256 * 2304 + (8 + 1) * 3 * 2304 * 1024
    assert blocks[0] == kda + 3 * 2304 * 9216 + 2 * 2304      # 103.2 M
    assert blocks[1] == blocks[2] == blocks[4] == kda + experts + 2 * 2304
    assert blocks[3] == mla + experts + 2 * 2304              # 93.4 M
    assert size(params["stem"]) + size(params["head"]) == 2 * 2304 * 20480 + 2304
    assert size(params) == 602_433_408
    assert state["blocks"]["1"]["router_bias"].shape == (256,)
    assert params["blocks"]["1"]["ffn"]["experts"]["w_in"].shape == (
        8, 2304, 2048)


def test_serving_this_family_is_refused_by_name(config, builder):
    for piece in ("serving_engine", "serving_widths"):
        with pytest.raises(NotImplementedError, match=f"kimi_linear.{piece}"):
            getattr(builder, piece)(config)
    with pytest.raises(NotImplementedError, match="decode_step_cost"):
        builder.decode_step_cost(config, 1.0, 1.0)


def test_the_kernels_costs_and_what_bounds_them(config, builder):
    from benchmark.harness.peaks import peaks_for

    s = builder.shape(config)
    peaks = peaks_for("TPU v5 lite")
    ops, nbytes = builder.kernel_cost("mla", s)
    pairs = 2 * 32 * 8192 * 8193 / 2
    assert ops == pairs * (8 * 192 + 6 * 128)
    assert nbytes == 16384 * 32 * (4 * 192 + 4 * 128) * 2
    assert ops / peaks.bf16_flops > 10 * nbytes / peaks.hbm_bytes_s
    ops, nbytes = builder.kernel_cost("moe", s)
    assert ops == 4 * 3 * 4096 * 2 * 3 * 2304 * 1024  # 4,096 rows a layer
    assert ops / peaks.bf16_flops > nbytes / peaks.hbm_bytes_s
    with pytest.raises(KeyError):
        builder.kernel_cost("kda", s)


def canned(builder, config, kernel_seconds):
    return {
        "shape": builder.shape(config),
        "device": {"kind": "TPU v5 lite", "count": 1},
        "epochs": [{"steps": 4, "traced": True, "wall_s": 8.0},
                   {"steps": 4, "traced": False, "wall_s": 7.2}],
        "device_trace": {"kernel_seconds": kernel_seconds,
                         "device0_busy_s": 7.2},
    }


def test_the_new_readers_on_a_canned_record(config, builder):
    record = canned(builder, config, {
        "jvp_mla_": 0.07, "mla": 0.26, "gmm": 0.03, "tgmm": 0.01,
        "custom-call": 1.0, "jvp__": 0.5})
    read = lambda name: manifest.load_module("per_layer", name).compute(record)
    assert read("mla_kernel_share") == pytest.approx(100 * 0.33 / 7.2)
    assert read("moe_kernel_share") == pytest.approx(100 * 0.04 / 7.2)
    ops, _ = builder.kernel_cost("mla", record["shape"])
    assert read("mla_kernel_roofline") == pytest.approx(
        100 * (ops / 197e12) * 4 / 0.33)
    ops, _ = builder.kernel_cost("moe", record["shape"])
    assert read("moe_kernel_roofline") == pytest.approx(
        100 * (ops / 197e12) * 4 / 0.04)
    assert 0 < read("mla_kernel_roofline") < 100
    assert 0 < read("moe_kernel_roofline") < 100


@pytest.mark.parametrize("name", [
    "mla_kernel_share", "mla_kernel_roofline",
    "moe_kernel_share", "moe_kernel_roofline"])
def test_the_new_readers_read_nothing_where_nothing_is(config, builder, name):
    reader = manifest.load_module("per_layer", name)
    # a traced record of another program: other kernels, no such builder
    other = canned(builder, config, {"jvp__": 0.05, "transpose_jvp___": 0.1})
    assert reader.compute(other) is None
    # a family whose builder states no cost for its kernels
    gpt = {**canned(builder, config, {"jvp_mla_": 0.1, "gmm": 0.1}),
           "shape": {"vocab_size": 50257}}
    if name.endswith("roofline"):
        assert reader.compute(gpt) is None
    assert reader.compute({**other, "device_trace": None}) is None
    assert kernels.named_seconds({}, "mla") == 0
