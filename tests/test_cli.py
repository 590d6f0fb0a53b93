"""Entry-point tests: both reference launch surfaces run end-to-end on the
8-device CPU mesh with synthetic data (nothing downloaded, SURVEY.md §4).

Runtime tests use the tinycnn smoke model (the 1-core CI host cannot
compile MobileNetV2 pipelines fast enough for the CPU backend's collective
rendezvous); the full MobileNetV2 paths are covered in test_pipeline.py /
test_data_parallel.py, and the reference ws=4 split is checked structurally
here.
"""

import os

import pytest

from distributed_model_parallel_tpu.cli import data_parallel, model_parallel


@pytest.mark.slow
def test_data_parallel_cli(tmp_path, monkeypatch):
    """Default-engine (declarative DP) data_parallel CLI e2e. `slow`
    (tier-1 budget); tier-1 twins: test_data_parallel_cli_ddp_syncbn
    and test_data_parallel_cli_ddp_overlapped drive the same entry
    point end to end (the DP engine's math stays pinned by
    tests/test_data_parallel.py)."""
    monkeypatch.chdir(tmp_path)
    result = data_parallel.main([
        "--lr", "0.1",
        "-type", "Synthetic",
        "-b", "64",
        "--val-batch-size", "128",
        "--epochs", "2",
        "--steps-per-epoch", "3",
        "--model", "tinycnn",
    ])
    assert len(result["history"]) == 2
    assert os.path.isfile(tmp_path / "log" / "data_para_64.txt")


def test_data_parallel_cli_ddp_syncbn(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    result = data_parallel.main([
        "--engine", "ddp", "--sync-bn", "--model", "tinycnn",
        "-type", "Synthetic", "-b", "64", "--val-batch-size", "128",
        "--epochs", "1", "--steps-per-epoch", "2",
    ])
    assert len(result["history"]) == 1


def test_data_parallel_cli_fsdp(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    result = data_parallel.main([
        "--engine", "fsdp", "--model", "tinycnn", "--optimizer", "adamw",
        "-type", "Synthetic", "-b", "64", "--val-batch-size", "128",
        "--epochs", "1", "--steps-per-epoch", "2", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1


@pytest.mark.slow
def test_data_parallel_cli_tp_collective_matmul(tmp_path, monkeypatch):
    """--engine tp --collective-matmul drives the full entry point on a
    (data, model) mesh with the chunked ppermute rings (a transformer
    model; the flag reaches the projections via Context.matmul).

    `slow` (tier-1 budget: the suite's single heaviest test, ~45 s of
    BERT jit on this host): the ring math keeps engine-level parity
    coverage in tier-1 (tests/test_collective_matmul.py), the lowering
    keeps its HLO pins (tests/test_collectives_hlo.py), the flag
    surface keeps its guards below, and the dryrun runs a
    tensor_parallel_collective_matmul leg every round."""
    monkeypatch.chdir(tmp_path)
    result = data_parallel.main([
        "--engine", "tp", "--model-shards", "4",
        "--collective-matmul",
        "--model", "bert_tiny",
        "-type", "SyntheticText",
        "-b", "16", "--val-batch-size", "16",
        "--epochs", "1", "--steps-per-epoch", "2",
        "--lr", "0.05",
    ])
    assert len(result["history"]) == 1


def test_collective_matmul_flag_guards():
    """Default off everywhere; misuse fails loudly instead of silently
    doing nothing: without --engine tp, without transformer projections,
    and under lm.py's pipeline mode."""
    from distributed_model_parallel_tpu.cli import lm

    assert not data_parallel.build_parser().parse_args(
        []
    ).collective_matmul
    assert not lm.build_parser().parse_args([]).collective_matmul
    with pytest.raises(SystemExit):  # needs --engine tp
        data_parallel.main([
            "--collective-matmul", "--model", "bert_tiny",
            "-type", "SyntheticText",
        ])
    with pytest.raises(SystemExit):  # no transformer projections
        data_parallel.main([
            "--engine", "tp", "--model-shards", "4",
            "--collective-matmul", "--model", "tinycnn",
            "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # plain tp on a CNN would silently
        data_parallel.main([      # replicate every weight (no rules hit)
            "--engine", "tp", "--model-shards", "4",
            "--model", "tinycnn", "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # pipeline mode has no 'seq' rings
        lm.main(["--pipeline-stages", "2", "--collective-matmul"])
    with pytest.raises(SystemExit):  # --model-shards is tp-only
        data_parallel.main([
            "--model-shards", "4", "--model", "tinycnn",
            "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # size-1 'seq' ring = silent no-op
        lm.main(["--collective-matmul"])
    with pytest.raises(SystemExit):  # size-1 'model' ring likewise
        data_parallel.main([
            "--engine", "tp", "--collective-matmul",
            "--model", "bert_tiny", "-type", "SyntheticText",
        ])


def test_data_parallel_cli_ddp_bucketed_hierarchical(
    tmp_path, monkeypatch
):
    """--engine ddp --grad-reduction bucketed --dcn-slices 2 drives the
    full entry point on the hybrid dcn×ici mesh with the flat-bucket
    ring reducer."""
    monkeypatch.chdir(tmp_path)
    result = data_parallel.main([
        "--engine", "ddp", "--grad-reduction", "bucketed",
        "--bucket-mb", "0.25", "--dcn-slices", "2",
        "--model", "tinycnn",
        "-type", "Synthetic", "-b", "64", "--val-batch-size", "128",
        "--epochs", "1", "--steps-per-epoch", "2",
    ])
    assert len(result["history"]) == 1


def test_data_parallel_cli_ddp_overlapped(tmp_path, monkeypatch):
    """--engine ddp --grad-reduction overlapped drives the full entry
    point: stagewise backward (2 segments over tinycnn's 4 blocks) with
    eager per-segment bucket firing on the hybrid dcn×ici mesh."""
    monkeypatch.chdir(tmp_path)
    result = data_parallel.main([
        "--engine", "ddp", "--grad-reduction", "overlapped",
        "--overlap-stages", "2", "--bucket-mb", "0.25",
        "--dcn-slices", "2", "--model", "tinycnn",
        "-type", "Synthetic", "-b", "64", "--val-batch-size", "128",
        "--epochs", "1", "--steps-per-epoch", "2",
    ])
    assert len(result["history"]) == 1


def test_grad_reduction_flag_guards():
    """Defaults stay monolithic/1-slice everywhere; misuse fails loudly
    instead of silently doing nothing."""
    from distributed_model_parallel_tpu.cli import lm

    dp_args = data_parallel.build_parser().parse_args([])
    assert dp_args.grad_reduction == "monolithic"
    # bucket_mb parses as a None sentinel ("flag not passed");
    # check_grad_reduction_args resolves it to the 25 MB default.
    assert dp_args.dcn_slices == 1 and dp_args.bucket_mb is None
    assert dp_args.overlap_stages is None
    lm_args = lm.build_parser().parse_args([])
    assert lm_args.grad_reduction == "monolithic"
    with pytest.raises(SystemExit):  # gspmd jit has no explicit site
        data_parallel.main([
            "--grad-reduction", "bucketed", "--model", "tinycnn",
            "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # --bucket-mb is bucketed-only
        data_parallel.main([
            "--engine", "ddp", "--bucket-mb", "5", "--model",
            "tinycnn", "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # even typed at the default value
        data_parallel.main([
            "--engine", "ddp", "--bucket-mb", "25", "--model",
            "tinycnn", "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # --dcn-slices not under tp
        data_parallel.main([
            "--engine", "tp", "--dcn-slices", "2",
            "--model", "bert_tiny", "-type", "SyntheticText",
        ])
    with pytest.raises(SystemExit):  # nonpositive bucket cap
        data_parallel.main([
            "--engine", "ddp", "--grad-reduction", "bucketed",
            "--bucket-mb", "0", "--model", "tinycnn",
            "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # pipeline mode reduces over wires
        lm.main([
            "--pipeline-stages", "2", "--grad-reduction", "bucketed",
        ])
    # dcn must divide the data axis (mesh-construction ValueError —
    # loud, with the dcn vocabulary, before any training work)
    with pytest.raises(ValueError, match="dcn"):
        data_parallel.main([
            "--engine", "ddp", "--dcn-slices", "3",
            "--model", "tinycnn", "-type", "Synthetic",
        ])


def test_overlapped_flag_guards():
    """--grad-reduction overlapped misuse fails fast (before datasets /
    meshes) on both CLIs: declarative engines have no explicit
    reduction site to re-stage, pipeline engines reduce over 'stage'
    wires, a 1-layer model has no second segment, and --overlap-stages
    is overlapped-only."""
    from distributed_model_parallel_tpu.cli import lm

    with pytest.raises(SystemExit):  # gspmd jit has no explicit site
        data_parallel.main([
            "--grad-reduction", "overlapped", "--model", "tinycnn",
            "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # neither does tp
        data_parallel.main([
            "--engine", "tp", "--grad-reduction", "overlapped",
            "--model", "bert_tiny", "-type", "SyntheticText",
        ])
    with pytest.raises(SystemExit):  # --overlap-stages is overlapped-only
        data_parallel.main([
            "--engine", "ddp", "--overlap-stages", "2",
            "--model", "tinycnn", "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # < 2 segments is the monolithic bwd
        data_parallel.main([
            "--engine", "ddp", "--grad-reduction", "overlapped",
            "--overlap-stages", "1", "--model", "tinycnn",
            "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # pipeline mode reduces over wires
        lm.main([
            "--pipeline-stages", "2", "--grad-reduction", "overlapped",
        ])
    with pytest.raises(SystemExit):  # 1 decoder layer: nothing to overlap
        lm.main([
            "--grad-reduction", "overlapped", "--layers", "1",
        ])
    with pytest.raises(SystemExit):  # more segments than decoder blocks
        lm.main([
            "--grad-reduction", "overlapped", "--layers", "2",
            "--overlap-stages", "4",
        ])


def test_dcn_compression_flag_guards():
    """--dcn-compression misuse fails fast, naming the flag and the
    fix: the wire codec targets the cross-slice hop, so it needs a
    'dcn'-factored mesh and an engine with an explicit dcn seam."""
    from distributed_model_parallel_tpu.cli import lm

    dp_args = data_parallel.build_parser().parse_args([])
    assert dp_args.dcn_compression == "none"
    assert lm.build_parser().parse_args([]).dcn_compression == "none"
    with pytest.raises(SystemExit):  # no 'dcn' axis to compress
        data_parallel.main([
            "--engine", "ddp", "--dcn-compression", "int8",
            "--model", "tinycnn", "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # gspmd jit has no explicit hop
        data_parallel.main([
            "--dcn-compression", "bf16", "--dcn-slices", "2",
            "--model", "tinycnn", "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit):  # neither does tp
        data_parallel.main([
            "--engine", "tp", "--dcn-compression", "bf16",
            "--dcn-slices", "2", "--model", "bert_tiny",
            "-type", "SyntheticText",
        ])
    with pytest.raises(SystemExit):  # lm: no 'dcn' axis to compress
        lm.main(["--dcn-compression", "bf16"])
    with pytest.raises(SystemExit):  # pipeline reduces over wires
        lm.main([
            "--pipeline-stages", "2", "--dcn-compression", "int8",
            "--dcn-slices", "2",
        ])
    with pytest.raises(SystemExit):  # gspmd MoE has no explicit hop
        lm.main([
            "--moe-experts", "8", "--dcn-compression", "int8",
            "--dcn-slices", "2",
        ])


def test_data_parallel_cli_ddp_quantized_dcn(tmp_path, monkeypatch):
    """--dcn-compression int8 drives the full entry point: bucketed
    hierarchical reducer on the 2x4 dcn×ici mesh with the int8 wire on
    the cross-slice hop (ops/wire_codec.py)."""
    monkeypatch.chdir(tmp_path)
    result = data_parallel.main([
        "--engine", "ddp", "--grad-reduction", "bucketed",
        "--bucket-mb", "0.25", "--dcn-slices", "2",
        "--dcn-compression", "int8", "--model", "tinycnn",
        "-type", "Synthetic", "-b", "64", "--val-batch-size", "128",
        "--epochs", "1", "--steps-per-epoch", "2",
    ])
    assert len(result["history"]) == 1


@pytest.mark.slow
def test_lm_cli_quantized_dcn_moe(tmp_path, monkeypatch):
    """--moe-dispatch hierarchical --dcn-compression bf16 reaches the
    expert-parallel LM engine end-to-end with the compressed dispatch
    wire. `slow` (tier-1 budget); tier-1 twins:
    test_data_parallel_cli_ddp_quantized_dcn (the flag surface e2e) and
    tests/test_wire_codec.py::test_ep_compressed_dispatch_matches_f32
    (the engine math)."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    result = lm.main([
        "--dim", "16", "--layers", "2", "--heads", "2",
        "--seq-len", "16", "-b", "8", "--epochs", "1",
        "--steps-per-epoch", "2", "--corpus-tokens", "2048",
        "--moe-experts", "8", "--moe-dispatch", "hierarchical",
        "--moe-overlap", "--dcn-slices", "2",
        "--dcn-compression", "bf16",
    ])
    assert len(result["history"]) == 1


@pytest.mark.slow
def test_lm_cli_bucketed(tmp_path, monkeypatch):
    """The lm CLI's --grad-reduction bucketed reaches the causal-LM
    sequence-parallel engine end-to-end (seq rings + data buckets;
    slow twin — the tier-1 reducer CLI coverage is the data_parallel
    bucketed-hierarchical row above)."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    result = lm.main([
        "--seq-shards", "2", "--grad-reduction", "bucketed",
        "--bucket-mb", "0.25", "--dcn-slices", "2",
        "--dim", "32", "--layers", "2", "--heads", "4",
        "--ffn-dim", "64", "--seq-len", "32",
        "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "4096", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1


@pytest.mark.slow
def test_lm_cli_overlapped(tmp_path, monkeypatch):
    """The lm CLI's --grad-reduction overlapped reaches the causal-LM
    sequence-parallel engine end-to-end (stagewise 'seq' psum + eager
    data buckets). `slow`; tier-1 twins: the engine-level parity case
    tests/test_grad_reduction.py::test_causal_lm_sp_overlapped_matches_
    monolithic and the data_parallel overlapped CLI row above."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    result = lm.main([
        "--seq-shards", "2", "--grad-reduction", "overlapped",
        "--overlap-stages", "2", "--bucket-mb", "0.25",
        "--dim", "32", "--layers", "2", "--heads", "4",
        "--ffn-dim", "64", "--seq-len", "32",
        "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "4096", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1


def test_lm_cli_moe_flag_guards():
    """The MoE flag surface fails fast with CLI vocabulary: exchange
    knobs without --moe-experts, MoE under seq/pipeline parallelism,
    overlap without hierarchical, expert-shards under hierarchical,
    reducer flags on the GSPMD EP engine, indivisible expert counts."""
    from distributed_model_parallel_tpu.cli import lm

    with pytest.raises(SystemExit):  # knob without --moe-experts
        lm.main(["--moe-dispatch", "hierarchical"])
    with pytest.raises(SystemExit):
        lm.main(["--moe-overlap"])
    with pytest.raises(SystemExit):
        lm.main(["--expert-shards", "2"])
    with pytest.raises(SystemExit):  # MoE x seq parallelism
        lm.main(["--moe-experts", "8", "--seq-shards", "2"])
    with pytest.raises(SystemExit):  # MoE x pipeline
        lm.main(["--moe-experts", "8", "--pipeline-stages", "2"])
    with pytest.raises(SystemExit):  # overlap needs hierarchical
        lm.main(["--moe-experts", "8", "--moe-overlap"])
    with pytest.raises(SystemExit):  # hierarchical x expert-shards
        lm.main([
            "--moe-experts", "8", "--moe-dispatch", "hierarchical",
            "--expert-shards", "2",
        ])
    with pytest.raises(SystemExit):  # EP engine is GSPMD — no reducer
        lm.main([
            "--moe-experts", "8", "--grad-reduction", "bucketed",
        ])
    with pytest.raises(SystemExit):  # MoE attends dense causal — a
        lm.main([                    # requested flash core would be
            "--moe-experts", "8",    # silently dropped
            "--attention", "ulysses_flash",
        ])
    with pytest.raises(SystemExit):  # 6 experts on the 8-way fabric
        lm.main([
            "--moe-experts", "6", "--moe-dispatch", "hierarchical",
        ])


def test_lm_cli_moe_hierarchical(tmp_path, monkeypatch):
    """--moe-experts --moe-dispatch hierarchical --moe-overlap drives
    the expert-parallel LM engine end-to-end on the hybrid dcn x ici
    fabric (the PR 10 tentpole's CLI surface)."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    result = lm.main([
        "--moe-experts", "8", "--moe-dispatch", "hierarchical",
        "--moe-overlap", "--dcn-slices", "2",
        "--dim", "16", "--layers", "2", "--heads", "2",
        "--ffn-dim", "32", "--seq-len", "16",
        "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "4096", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1


@pytest.mark.slow
def test_lm_cli_moe_gspmd(tmp_path, monkeypatch):
    """--moe-experts with the default gspmd dispatch drives the
    'expert'-axis layout end-to-end. `slow`; tier-1 twins: the
    hierarchical CLI row above and the engine-level parity in
    tests/test_expert_dispatch.py."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    result = lm.main([
        "--moe-experts", "4", "--expert-shards", "4",
        "--dim", "16", "--layers", "2", "--heads", "2",
        "--ffn-dim", "32", "--seq-len", "16",
        "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "4096", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1


@pytest.mark.slow
def test_lm_cli_collective_matmul(tmp_path, monkeypatch):
    """The lm CLI's --collective-matmul reaches the sequence-parallel
    engine's FFN rings end-to-end. `slow` (tier-1 budget): engine-level
    ring parity stays in tier-1 via
    tests/test_collective_matmul.py::test_lm_sp_collective_matmul_
    matches_ring_engine, and the flag guards above stay."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    result = lm.main([
        "--seq-shards", "4", "--collective-matmul",
        "--dim", "32", "--layers", "2", "--heads", "4",
        "--ffn-dim", "64", "--seq-len", "32",
        "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "4096", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1


@pytest.mark.slow
def test_model_parallel_cli(tmp_path, monkeypatch):
    """Default-schedule (gpipe) model_parallel CLI e2e incl. the
    log/64.txt side effect. `slow` (tier-1 budget); tier-1 twin:
    test_model_parallel_cli_1f1b drives the same entry point end to end
    (gpipe engine math stays pinned by the tests/test_pipeline.py
    engine rows)."""
    monkeypatch.chdir(tmp_path)
    result = model_parallel.main([
        "./data",
        "-type", "Synthetic",
        "--world-size", "4",
        "--dist-backend", "nccl",  # launch-line compatibility: maps to xla
        "--model", "tinycnn",
        "--microbatches", "2",
        "-b", "64",
        "--epochs", "1",
        "--steps-per-epoch", "2",
        "--lr", "0.1",
    ])
    assert len(result["history"]) == 1
    assert os.path.isfile(tmp_path / "log" / "64.txt")


@pytest.mark.slow
def test_model_parallel_cli_1f1b(tmp_path, monkeypatch):
    """--pipeline-schedule 1f1b drives the full entry point; default
    stays gpipe (no behavior change for existing launch lines).
    `slow` (tier-1 budget); tier-1 twins:
    test_pipeline_schedule's 1f1b-vs-gpipe parity + BN running-stats
    pins (the schedule math) — the flag surface itself is covered by
    the schedule guard tests."""
    monkeypatch.chdir(tmp_path)
    result = model_parallel.main([
        "./data",
        "-type", "Synthetic",
        "--world-size", "4",
        "--model", "tinycnn",
        "--microbatches", "2",
        "--pipeline-schedule", "1f1b",
        "-b", "64",
        "--epochs", "1",
        "--steps-per-epoch", "2",
        "--lr", "0.1",
    ])
    assert len(result["history"]) == 1


@pytest.mark.slow
def test_model_parallel_cli_interleaved(tmp_path, monkeypatch):
    """--pipeline-schedule interleaved --virtual-stages 2 drives the
    full entry point: 2 physical stages x 2 chunks = a 4-way tinycnn
    split dealt round-robin, ring-routed activations, train + eval
    epochs. `slow` (tier-1 budget); tier-1 twins:
    test_model_parallel_cli_1f1b (same entry point + schedule-flag
    plumbing) and test_pipeline_schedule.py::
    test_interleaved_matches_gpipe_1f1b_and_dense_smoke (the
    interleaved engine math)."""
    monkeypatch.chdir(tmp_path)
    result = model_parallel.main([
        "./data",
        "-type", "Synthetic",
        "--world-size", "2",
        "--model", "tinycnn",
        "--microbatches", "2",
        "--pipeline-schedule", "interleaved",
        "--virtual-stages", "2",
        "-b", "64",
        "--epochs", "1",
        "--steps-per-epoch", "2",
        "--lr", "0.1",
    ])
    assert len(result["history"]) == 1


@pytest.mark.slow
def test_lm_cli_interleaved(tmp_path, monkeypatch):
    """The lm CLI's interleaved pipeline: 4 decoder-block chunks over 2
    stages, token-level head on the last logical chunk (slow twin: the
    tier-1 interleaved CLI coverage is the model_parallel row above)."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    result = lm.main([
        "--pipeline-stages", "2",
        "--pipeline-schedule", "interleaved",
        "--virtual-stages", "2",
        "--microbatches", "2",
        "--dim", "16", "--layers", "4", "--heads", "2",
        "--ffn-dim", "32", "--seq-len", "16", "--vocab-size", "64",
        "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "2048", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1


def test_interleaved_flag_guards():
    """--virtual-stages misuse fails loudly instead of silently doing
    nothing, on both CLIs."""
    from distributed_model_parallel_tpu.cli import lm

    assert model_parallel.build_parser().parse_args(
        ["./data"]
    ).virtual_stages == 1
    assert lm.build_parser().parse_args([]).virtual_stages == 1
    with pytest.raises(SystemExit):  # V > 1 needs interleaved schedule
        model_parallel.main([
            "./data", "-type", "Synthetic", "--world-size", "2",
            "--model", "tinycnn", "--virtual-stages", "2",
        ])
    with pytest.raises(SystemExit):  # interleaved needs >= 2 stages
        model_parallel.main([
            "./data", "-type", "Synthetic", "--model", "tinycnn",
            "--pipeline-schedule", "interleaved",
        ])
    with pytest.raises(SystemExit):  # M must divide by S when V > 1
        model_parallel.main([
            "./data", "-type", "Synthetic", "--world-size", "2",
            "--model", "tinycnn", "--pipeline-schedule", "interleaved",
            "--virtual-stages", "2", "--microbatches", "3",
        ])
    with pytest.raises(SystemExit):  # reference split is a 4-chunk plan
        model_parallel.build_stages("mobilenetv2", 4, 10, True, 2)
    with pytest.raises(SystemExit):  # V without pipeline mode (lm)
        lm.main(["--virtual-stages", "2"])
    with pytest.raises(SystemExit):  # S*V chunks > layers
        lm.main([
            "--pipeline-stages", "2", "--pipeline-schedule",
            "interleaved", "--virtual-stages", "2", "--layers", "3",
            "--microbatches", "2",
        ])


def test_pipeline_schedule_flag_defaults():
    """Both pipeline-capable CLIs expose --pipeline-schedule, defaulting
    to gpipe; lm.py rejects the flag without pipeline stages (it would
    silently do nothing)."""
    from distributed_model_parallel_tpu.cli import lm

    args = model_parallel.build_parser().parse_args(
        ["./data", "--world-size", "4"]
    )
    assert args.pipeline_schedule == "gpipe"
    args = lm.build_parser().parse_args([])
    assert args.pipeline_schedule == "gpipe"
    args = lm.build_parser().parse_args(
        ["--pipeline-stages", "2", "--pipeline-schedule", "1f1b"]
    )
    assert args.pipeline_schedule == "1f1b"
    with pytest.raises(SystemExit):
        lm.main(["--pipeline-schedule", "1f1b"])  # no --pipeline-stages


def test_serve_cli_replicated(tmp_path):
    """The serving CLI end-to-end: synthetic trace in, per-request
    latencies + aggregate tokens/sec / p50/p99 legs out, slot
    recycling under admission pressure (6 requests over 2 slots),
    plus the --metrics-out export (what tools/obsreport --metrics
    ingests)."""
    import json

    from distributed_model_parallel_tpu.cli import serve
    from distributed_model_parallel_tpu.observability import metrics

    mpath = tmp_path / "metrics.json"
    try:
        result = serve.main([
            "--dim", "16", "--layers", "2", "--heads", "4",
            "--ffn-dim", "32", "--vocab-size", "61",
            "--num-slots", "2", "--max-len", "16", "--prefill-len", "8",
            "--num-requests", "6", "--prompt-len-min", "2",
            "--prompt-len-max", "6", "--max-new-tokens", "3",
            "--metrics-out", str(mpath),
        ])
    finally:
        metrics.set_metrics(None)  # --metrics-out enabled the global
    assert result["serving"]["requests"] == 6
    assert result["serving"]["generated_tokens"] == 18
    assert result["serving"]["decode_p50_ms"] is not None
    assert len(result["requests"]) == 6
    with open(mpath) as f:
        exported = json.load(f)
    assert {
        "serve_queued_s", "serve_ttft_s", "serve_token_s",
    } <= set(exported["histograms"])
    assert exported["histograms"]["serve_ttft_s"]["count"] == 6
    assert exported["gauges"]["serve_goodput"] > 0
    # The counter totals to the report's generated_tokens exactly
    # (prefill's first token + one per active slot per decode step).
    assert exported["counters"]["serve_tokens_total"] == 18


def test_serve_cli_says_once_which_program_a_chunks_recurrence_runs_as(
        tmp_path, capsys):
    """`--model-config` with a family that keeps a state: one start-up
    line names the program the chunk step's state layers run their
    recurrence as at --prefill-chunk (off a TPU: the loop), from the
    engine's own field; the gauge says the same and `paged` counts the
    chunks dispatched to the kernel program. A GPT run prints no such
    line."""
    import json

    from distributed_model_parallel_tpu.cli import serve
    from distributed_model_parallel_tpu.observability import metrics

    config = tmp_path / "config.json"
    config.write_text(json.dumps({
        "model_type": "jamba", "vocab_size": 97, "hidden_size": 32,
        "num_hidden_layers": 3, "num_attention_heads": 4,
        "num_key_value_heads": 1, "intermediate_size": 64,
        "attn_layer_period": 3, "attn_layer_offset": 1,
        "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_dt_rank": 6,
        "mamba_expand": 2, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 64,
    }))
    drain = [
        "--num-slots", "2", "--max-len", "32", "--prefill-len", "32",
        "--page-size", "4", "--prefill-chunk", "8", "--num-requests", "3",
        "--prompt-len-min", "2", "--prompt-len-max", "12",
        "--max-new-tokens", "3",
    ]
    mpath = tmp_path / "metrics.json"
    try:
        result = serve.main(["--model-config", str(config), *drain,
                             "--metrics-out", str(mpath)])
    finally:
        metrics.set_metrics(None)
    out = capsys.readouterr().out
    line = ("==> jamba: a chunk of 8 positions runs the state layers' "
            "recurrence as the loop")
    assert out.count(line) == 1 and out.count("recurrence as the") == 1
    assert result["serving"]["paged"]["state_kernel_chunks"] == 0
    with open(mpath) as f:
        assert json.load(f)["gauges"]["serve_state_scan_kernel"] == 0.0
    assert "serve_state_scan_kernel" in metrics.METRIC_NAMES
    serve.main(["--dim", "16", "--layers", "2", "--heads", "4",
                "--ffn-dim", "32", "--vocab-size", "61", *drain])
    assert "recurrence as the" not in capsys.readouterr().out


@pytest.mark.slow
def test_serve_cli_tp_collective_matmul():
    """--layout tp --collective-matmul drives the full serving entry
    point with the opted-in decode rings. `slow` (tier-1 budget);
    tier-1 twins: tests/test_serving.py::
    test_decode_matches_dense_tp_collective_matmul (the engine math),
    the serve/S2/cm hlolint combo (the lowering), and
    test_serve_cli_replicated + test_serving_flag_guards (the entry
    point and flag surface)."""
    from distributed_model_parallel_tpu.cli import serve

    result = serve.main([
        "--layout", "tp", "--model-shards", "4", "--collective-matmul",
        "--dim", "16", "--layers", "2", "--heads", "4",
        "--ffn-dim", "32", "--vocab-size", "61",
        "--num-slots", "4", "--max-len", "16", "--prefill-len", "8",
        "--num-requests", "4", "--prompt-len-min", "2",
        "--prompt-len-max", "6", "--max-new-tokens", "3",
    ])
    assert result["serving"]["requests"] == 4
    assert result["serving"]["collective_matmul"] is True


def test_serve_cli_sp():
    """--layout sp drives the full serving entry point: ring-attention
    prefill + online-softmax decode over the 'seq'-sharded cache."""
    from distributed_model_parallel_tpu.cli import serve

    result = serve.main([
        "--layout", "sp", "--seq-shards", "4",
        "--dim", "16", "--layers", "2", "--heads", "4",
        "--ffn-dim", "32", "--vocab-size", "61",
        "--num-slots", "4", "--max-len", "16", "--prefill-len", "8",
        "--num-requests", "4", "--prompt-len-min", "2",
        "--prompt-len-max", "6", "--max-new-tokens", "3",
    ])
    assert result["serving"]["requests"] == 4
    assert result["serving"]["layout"] == "sp"


def test_serving_flag_guards():
    """Serving rejects training-side flags and inconsistent layouts
    loudly, BEFORE building meshes/engines (cli/common.
    check_serving_args): a launch line pasted from the training CLIs
    must fail with an explanation, not silently do nothing."""
    from distributed_model_parallel_tpu.cli import serve

    args = serve.build_parser().parse_args([])
    assert args.layout == "replicated"
    assert not args.collective_matmul
    with pytest.raises(SystemExit):  # serving has no stage wires
        serve.main(["--pipeline-stages", "2"])
    with pytest.raises(SystemExit):  # no backward to reduce
        serve.main(["--grad-reduction", "bucketed"])
    with pytest.raises(SystemExit):  # even typed at the default value
        serve.main(["--bucket-mb", "25"])
    with pytest.raises(SystemExit):  # overlap is a backward knob
        serve.main(["--overlap-stages", "2"])
    with pytest.raises(SystemExit):  # serving meshes are model/seq
        serve.main(["--dcn-slices", "2"])
    with pytest.raises(SystemExit):  # no dcn fabric to compress
        serve.main(["--dcn-compression", "int8"])
    with pytest.raises(SystemExit):  # rings need the tp layout
        serve.main(["--collective-matmul"])
    with pytest.raises(SystemExit):  # tp with 1 shard = replicated
        serve.main(["--layout", "tp"])
    with pytest.raises(SystemExit):  # sp with 1 shard = replicated
        serve.main(["--layout", "sp"])
    with pytest.raises(SystemExit):  # one layout per run
        serve.main(["--layout", "sp", "--seq-shards", "2",
                    "--model-shards", "2"])
    with pytest.raises(SystemExit):  # shards without a layout
        serve.main(["--model-shards", "4"])
    with pytest.raises(SystemExit):  # prompts must fit the prefill pad
        serve.main(["--prompt-len-max", "200", "--prefill-len", "64"])
    # --- paged-cache knobs (ISSUE 15) ---
    with pytest.raises(SystemExit):  # page must divide max_len
        serve.main(["--page-size", "48", "--max-len", "64"])
    with pytest.raises(SystemExit):  # chunking needs the paged layout
        serve.main(["--prefill-chunk", "16"])
    with pytest.raises(SystemExit):  # pool sizing needs the paged layout
        serve.main(["--kv-pages", "8"])
    with pytest.raises(SystemExit):  # sharing needs pages
        serve.main(["--prefix-cache"])
    with pytest.raises(SystemExit):  # prefix cache needs chunked ingest
        serve.main(["--page-size", "16", "--prefix-cache"])
    with pytest.raises(SystemExit):  # no chunked ingest under sp
        serve.main(["--layout", "sp", "--seq-shards", "2",
                    "--page-size", "16", "--prefill-chunk", "8"])
    with pytest.raises(SystemExit):  # no page sharing under sp
        serve.main(["--layout", "sp", "--seq-shards", "2",
                    "--page-size", "16", "--prefill-chunk", "8",
                    "--prefix-cache"])
    with pytest.raises(SystemExit):  # page must split over seq shards
        serve.main(["--layout", "sp", "--seq-shards", "4",
                    "--page-size", "2", "--max-len", "64"])
    # --- sampling knobs ---
    with pytest.raises(SystemExit):  # top-k filters a sampling dist
        serve.main(["--top-k", "8"])
    with pytest.raises(SystemExit):  # top-p likewise
        serve.main(["--top-p", "0.9"])
    with pytest.raises(SystemExit):  # temperature >= 0
        serve.main(["--temperature", "-1"])
    with pytest.raises(SystemExit):  # top-p in (0, 1]
        serve.main(["--temperature", "1", "--top-p", "1.5"])


def test_serve_cli_paged_prefix(tmp_path):
    """The paged serving surface end-to-end (tier-1): --page-size +
    --prefill-chunk + --prefix-cache through the full CLI with
    --metrics-out — the report carries the page-pool accounting and
    prefix stats, and the new serve_kv_pages_in_use /
    serve_prefix_hits_total series land on the exposition surface."""
    import json

    from distributed_model_parallel_tpu.cli import serve
    from distributed_model_parallel_tpu.observability import metrics

    mpath = tmp_path / "metrics.json"
    try:
        result = serve.main([
            "--dim", "16", "--layers", "2", "--heads", "4",
            "--ffn-dim", "32", "--vocab-size", "61",
            "--num-slots", "2", "--max-len", "16", "--prefill-len", "8",
            "--page-size", "4", "--prefill-chunk", "4",
            "--prefix-cache",
            "--num-requests", "6", "--prompt-len-min", "2",
            "--prompt-len-max", "6", "--max-new-tokens", "3",
            "--metrics-out", str(mpath),
        ])
    finally:
        metrics.set_metrics(None)
    srv = result["serving"]
    assert srv["requests"] == 6
    assert srv["page_size"] == 4 and srv["prefill_chunk"] == 4
    assert srv["paged"]["pages_in_use_peak"] >= 1
    # Bounded by the pool; the strict tokens-not-stripes pin lives in
    # tests/test_serving_paged.py (the prefix cache deliberately KEEPS
    # finished prompts' pages live for reuse, so a cache-on run may
    # fill the pool).
    assert srv["paged"]["kv_cache_bytes_peak"] <= \
        srv["paged"]["contiguous_bytes"]
    assert "prefix_cache" in srv
    with open(mpath) as f:
        exported = json.load(f)
    assert "serve_kv_pages_in_use" in exported["gauges"]
    assert "serve_prefix_hits_total" in exported["counters"]


def test_serve_cli_sampling_greedy_bitstable():
    """--temperature 0 (the default) is bit-stable: the sampled-path
    flags left at their defaults produce byte-identical tokens to a
    plain greedy run, and a --temperature run is deterministic for a
    fixed --seed (per-slot PRNG lanes, serving/sampling.py)."""
    from distributed_model_parallel_tpu.cli import serve

    base = [
        "--dim", "16", "--layers", "2", "--heads", "4",
        "--ffn-dim", "32", "--vocab-size", "61",
        "--num-slots", "2", "--max-len", "16", "--prefill-len", "8",
        "--num-requests", "3", "--prompt-len-min", "2",
        "--prompt-len-max", "6", "--max-new-tokens", "3",
    ]
    greedy = serve.main(base)
    greedy2 = serve.main(base + ["--temperature", "0"])
    assert [r["tokens"] for r in greedy["requests"]] == \
        [r["tokens"] for r in greedy2["requests"]]
    s1 = serve.main(base + ["--temperature", "0.8", "--top-k", "16",
                            "--top-p", "0.95"])
    s2 = serve.main(base + ["--temperature", "0.8", "--top-k", "16",
                            "--top-p", "0.95"])
    assert [r["tokens"] for r in s1["requests"]] == \
        [r["tokens"] for r in s2["requests"]]
    assert s1["serving"]["temperature"] == 0.8


def test_reference_split_builds_stages():
    """The ws=4 reference boundaries produce 4 composable stages
    (structural check; the compiled path runs in test_pipeline.py)."""
    stages = model_parallel.build_stages("mobilenetv2", 4, 10, True)
    assert len(stages) == 4


def test_model_parallel_rejects_bad_reference_split():
    with pytest.raises(SystemExit):
        model_parallel.build_stages("mobilenetv2", 2, 10, True)
    with pytest.raises(SystemExit):
        model_parallel.build_stages("resnet18", 4, 10, True)


# --------------------------------------------- checkpoint flag surface


def test_serve_cli_trained_checkpoint(tmp_path, monkeypatch):
    """Train 1 epoch of a tinycnn-scale GPT (lm CLI, sharded format),
    then `serve --checkpoint`: the served generations must MATCH an
    in-process ServingEngine fed the independently restored params —
    the file round trip and the canonical placement add nothing."""
    import jax
    import jax.numpy as jnp

    from distributed_model_parallel_tpu.checkpointing import (
        restore_subtree,
    )
    from distributed_model_parallel_tpu.cli import lm, serve
    from distributed_model_parallel_tpu.models.gpt import GPTConfig
    from distributed_model_parallel_tpu.serving.engine import ServingEngine

    monkeypatch.chdir(tmp_path)
    lm.main([
        "--dim", "16", "--layers", "2", "--heads", "2",
        "--ffn-dim", "32", "--seq-len", "16", "--vocab-size", "61",
        "-b", "16", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "2048",
        "--checkpoint-dir", "./ck", "--checkpoint-format", "sharded",
    ])
    serve_flags = [
        "--dim", "16", "--layers", "2", "--heads", "2",
        "--ffn-dim", "32", "--vocab-size", "61",
        "--num-slots", "2", "--max-len", "16", "--prefill-len", "8",
        "--num-requests", "3", "--prompt-len-min", "2",
        "--prompt-len-max", "6", "--max-new-tokens", "3",
    ]
    result = serve.main(["--checkpoint", "./ck"] + serve_flags)
    assert result["serving"]["checkpoint"] == "./ck"
    assert len(result["requests"]) == 3

    # In-process twin: restore the params subtree directly and run the
    # same trace through a fresh engine.
    cfg = GPTConfig(
        vocab_size=61, dim=16, num_layers=2, num_heads=2, ffn_dim=32,
        max_position=16, dropout_rate=0.0, pad_token_id=0,
    )
    eng = ServingEngine(
        cfg, None, layout="replicated", num_slots=2, max_len=16,
        prefill_len=8,
    )
    key_aval = jax.ShapeDtypeStruct((2,), jnp.uint32)
    p_aval, _ = jax.eval_shape(eng._full.init, key_aval)
    params, meta = restore_subtree("./ck", p_aval, name="ckpt")
    assert meta["gpt_config"]["dim"] == 16
    args = serve.build_parser().parse_args(serve_flags)
    sched = eng.run(eng.place_params(params), serve.synthetic_trace(args))
    by_rid = {f.rid: [int(t) for t in f.tokens] for f in sched.finished}
    for r in result["requests"]:
        # Greedy token-id parity == logit parity for the served model.
        assert r["tokens"] == by_rid[r["rid"]]


def test_serve_cli_checkpoint_config_guard(tmp_path, monkeypatch):
    """--checkpoint fails fast NAMING the mismatched field (and its
    serve flag) when the recorded gpt_config disagrees, and complains
    about absent checkpoints before building an engine. The guard
    reads only metadata, so the checkpoint here is written directly
    (no training) — the full lm-train -> serve loop is
    test_serve_cli_trained_checkpoint."""
    import jax

    from distributed_model_parallel_tpu.checkpointing import save_sharded
    from distributed_model_parallel_tpu.cli import serve

    monkeypatch.chdir(tmp_path)
    with pytest.raises(SystemExit, match="no checkpoint"):
        serve.main(["--checkpoint", "./nope", "--dim", "16",
                    "--layers", "2", "--heads", "2"])
    save_sharded(
        "./ck", {"params": {"w": jax.numpy.zeros((2, 2))}},
        acc=0.0, epoch=0,
        extra={"gpt_config": {
            "vocab_size": 61, "dim": 16, "num_layers": 2,
            "num_heads": 2, "ffn_dim": 32, "max_position": 16,
        }},
    )
    with pytest.raises(SystemExit, match=r"dim=16.*--dim"):
        serve.main([
            "--checkpoint", "./ck", "--dim", "32", "--layers", "2",
            "--heads", "2", "--vocab-size", "61", "--max-len", "16",
        ])
    with pytest.raises(SystemExit, match=r"max_position=16.*--max-len"):
        serve.main([
            "--checkpoint", "./ck", "--dim", "16",
            "--layers", "2", "--heads", "2", "--ffn-dim", "32",
            "--vocab-size", "61", "--max-len", "32",
        ])


def test_training_cli_async_save_guards(tmp_path, monkeypatch):
    """--async-save without --checkpoint-format sharded fails at flag
    validation on BOTH training CLIs, before datasets/meshes build."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    with pytest.raises(SystemExit, match="async-save"):
        data_parallel.main([
            "--async-save", "-type", "Synthetic", "--model", "tinycnn",
        ])
    with pytest.raises(SystemExit, match="async-save"):
        lm.main(["--async-save"])


@pytest.mark.slow
def test_data_parallel_cli_fsdp_sharded_async(tmp_path, monkeypatch):
    """FSDP + --checkpoint-format sharded --async-save end to end: the
    run writes a manifest + per-process shard files (no .npz), and a
    --resume run restores from them. `slow` (tier-1 budget: two FSDP
    CLI mains); tier-1 twins: test_data_parallel_cli_fsdp (the CLI
    path), tests/test_trainer.py::
    test_trainer_sharded_format_saves_and_resumes (the sharded
    save/resume machinery) and test_training_cli_async_save_guards
    (the flag surface)."""
    from distributed_model_parallel_tpu.checkpointing import (
        manifest_exists,
    )

    monkeypatch.chdir(tmp_path)
    result = data_parallel.main([
        "--engine", "fsdp", "--model", "tinycnn",
        "-type", "Synthetic", "-b", "64", "--val-batch-size", "128",
        "--epochs", "1", "--steps-per-epoch", "2",
        "--checkpoint-format", "sharded", "--async-save",
        "--max-restarts", "1",
    ])
    assert len(result["history"]) == 1
    assert manifest_exists("./checkpoint", "last")
    assert not os.path.isfile(tmp_path / "checkpoint" / "last.npz")
    resumed = data_parallel.main([
        "--engine", "fsdp", "--model", "tinycnn", "--resume",
        "-type", "Synthetic", "-b", "64", "--val-batch-size", "128",
        "--epochs", "2", "--steps-per-epoch", "2",
        "--checkpoint-format", "sharded",
    ])
    assert [h["epoch"] for h in resumed["history"]] == [1]


# ------------------------------------------------------ --plan (ISSUE 19)


def test_lm_cli_plan_flag_guards():
    """The --plan surface fails fast with CLI vocabulary: bad specs,
    conflicts with the hand-set factorization/schedule flags it
    replaces, the expert surface, sp=1 ring knobs, reducer flags on
    the fused-psum engine, --dcn-slices on the stage-major mesh, and
    device/batch/seq-divisibility violations — each named after the
    plan field that rules it."""
    from distributed_model_parallel_tpu.cli import lm

    with pytest.raises(SystemExit, match="bad plan token"):
        lm.main(["--plan", "zz4"])
    with pytest.raises(SystemExit, match="IS the mesh factorization"):
        lm.main(["--plan", "pp2xdp4", "--pipeline-stages", "2"])
    with pytest.raises(SystemExit, match="IS the mesh factorization"):
        lm.main(["--plan", "sp2xdp4", "--seq-shards", "2"])
    with pytest.raises(SystemExit, match="pp token's suffix"):
        lm.main(["--plan", "pp2xdp4",
                 "--pipeline-schedule", "interleaved"])
    with pytest.raises(SystemExit, match="has pp=1"):
        lm.main(["--plan", "dp8", "--microbatches", "4"])
    with pytest.raises(SystemExit, match="expert surface"):
        lm.main(["--plan", "ep2xdp4"])
    with pytest.raises(SystemExit, match=r"ParallelPlan\.ep=1"):
        lm.main(["--plan", "dp8", "--moe-experts", "8"])
    with pytest.raises(SystemExit, match="sp=1"):
        lm.main(["--plan", "pp2xdp4", "--attention", "ring_flash"])
    with pytest.raises(SystemExit, match="sp=1"):
        lm.main(["--plan", "pp2xdp4", "--collective-matmul"])
    with pytest.raises(SystemExit, match="ONE fused psum"):
        lm.main(["--plan", "pp2xdp4",
                 "--grad-reduction", "bucketed"])
    with pytest.raises(SystemExit, match="stage-major"):
        lm.main(["--plan", "pp2xdp4", "--dcn-slices", "2"])
    with pytest.raises(SystemExit, match="device"):
        lm.main(["--plan", "pp4xsp4xdp4"])  # 64 > 8 devices
    with pytest.raises(SystemExit, match="must divide"):
        lm.main(["--plan", "pp2xdp4", "-b", "9",
                 "--corpus-tokens", "4096"])
    with pytest.raises(SystemExit, match="seq"):
        lm.main(["--plan", "sp4xdp2", "--seq-len", "30",
                 "-b", "8", "--corpus-tokens", "4096"])


@pytest.mark.parametrize("cli, argv, said", [
    ("lm", ["--auto-tune", "search"], "unrecognized arguments"),
    ("lm", ["--plan", "auto"], "bad plan token"),
    ("data_parallel", ["--auto-tune", "search"],
     "unrecognized arguments"),
], ids=["lm --auto-tune search", "lm --plan auto",
        "data_parallel --auto-tune search"])
def test_removed_tuner_switches_are_refused(cli, argv, said, capsys):
    """No model of speed sets a knob: the tuner's switches are gone
    from both training parsers, refused as any unknown flag or plan
    token is, before a backend starts."""
    from distributed_model_parallel_tpu.cli import lm

    main = {"lm": lm.main, "data_parallel": data_parallel.main}[cli]
    with pytest.raises(SystemExit) as exc:
        main(argv)
    assert exc.value.code not in (0, None)
    assert said in str(exc.value.code) + capsys.readouterr().err


def test_lm_cli_scheduled_plan_guards():
    """The scheduled --plan grammar's refusal paths (ISSUE 20), each
    naming the offending plan FIELD and the flag that sets it: the
    suffix rides only the pp token, V=1 interleaving is spelled 1f1b,
    a pp=1 plan cannot be scheduled, the hand-set schedule flags stay
    mutually exclusive with a scheduled spec, and the engine's
    fail-fast bounds (M >= pp*V for interleaved; pp*V must divide the
    block count) surface through the CLI with --microbatches and
    --layers named."""
    from distributed_model_parallel_tpu.cli import lm

    with pytest.raises(SystemExit, match="schedule suffix"):
        lm.main(["--plan", "sp2-1f1bxdp4"])  # suffix off the pp token
    with pytest.raises(SystemExit, match="1f1b"):
        lm.main(["--plan", "pp2-int1xdp4"])  # V=1 interleaving
    with pytest.raises(SystemExit, match="pp token"):
        lm.main(["--plan", "pp1-1f1bxdp8"])  # nothing to schedule
    with pytest.raises(SystemExit, match="pp token's suffix"):
        lm.main(["--plan", "pp2-1f1bxdp4",
                 "--pipeline-schedule", "1f1b"])  # spec owns it
    with pytest.raises(SystemExit, match="pp token's suffix"):
        lm.main(["--plan", "pp2-int2xdp2", "--virtual-stages", "2"])
    with pytest.raises(SystemExit, match="--microbatches"):
        lm.main(["--plan", "pp2-int2xdp2", "--microbatches", "2",
                 "--corpus-tokens", "4096"])  # M=2 < pp*V=4
    with pytest.raises(SystemExit, match="--layers"):
        lm.main(["--plan", "pp2-int2xdp2", "--layers", "6",
                 "--corpus-tokens", "4096"])  # 6 blocks into 4 chunks
    # The interleaved default M is pp*V (not pp): batch divisibility
    # is checked against the schedule-aware microbatch count.
    with pytest.raises(SystemExit, match="must divide"):
        lm.main(["--plan", "pp2-int2xdp2", "-b", "12",
                 "--corpus-tokens", "4096"])  # 12 % (4*2) != 0


def test_lm_cli_composed_plan_e2e(tmp_path, monkeypatch):
    """`--plan pp2xsp2xdp2` trains the composed 3-axis engine end to
    end through the lm CLI (the ISSUE 19 acceptance surface)."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    result = lm.main([
        "--plan", "pp2xsp2xdp2",
        "--dim", "32", "--layers", "2", "--heads", "4",
        "--ffn-dim", "64", "--seq-len", "32",
        "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "4096", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1


def test_lm_cli_plan_says_which_local_attention(
    tmp_path, monkeypatch, capsys
):
    """A plan whose sequence is whole on a chip (`fsdp4`): the CLI
    prints the engine's pick once beside the plan, and the Trainer sets
    the gauge. Off a TPU both say dense: the compiled step is the one
    the plan parities pin."""
    from distributed_model_parallel_tpu.cli import lm
    from distributed_model_parallel_tpu.observability import metrics

    monkeypatch.chdir(tmp_path)
    registry = metrics.MetricsRegistry(enabled=True)
    metrics.set_metrics(registry)
    try:
        result = lm.main([
            "--plan", "fsdp4", "--remat",
            "--dim", "32", "--layers", "2", "--heads", "4",
            "--ffn-dim", "64", "--seq-len", "32",
            "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
            "--corpus-tokens", "4096", "--lr", "1e-3",
        ])
    finally:
        metrics.set_metrics(None)
    assert len(result["history"]) == 1
    out = capsys.readouterr().out
    assert out.count("local attention: dense") == 1
    assert "plan fsdp4" in out
    # ...and where an fsdp plan gathers and reduces (ISSUE 33), from
    # the engine's own field: a log tells the per-block program from
    # the whole-model one without a trace
    assert out.count(
        "plan fsdp4: parameters 1/4 over 'data', all-gather float32 per "
        "block, one ahead; reduce-scatter float32"
    ) == 1
    assert registry._gauges["train_local_attention_flash"].value == 0.0
    assert "train_local_attention_flash" in metrics.METRIC_NAMES


def test_lm_cli_plan_now_legal_combos(tmp_path, monkeypatch):
    """Combos the pre-plan guards refused are legal under a plan that
    licenses them: --microbatches with a ppN plan (the composed tick
    loop's M), and ring attention knobs with an spN plan."""
    from distributed_model_parallel_tpu.cli import lm

    monkeypatch.chdir(tmp_path)
    result = lm.main([
        "--plan", "pp2xdp2", "--microbatches", "4",
        "--dim", "32", "--layers", "2", "--heads", "4",
        "--ffn-dim", "64", "--seq-len", "32",
        "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "4096", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1
    result = lm.main([
        "--plan", "sp2xdp2", "--attention", "ring_flash",
        "--collective-matmul",
        "--dim", "32", "--layers", "2", "--heads", "4",
        "--ffn-dim", "64", "--seq-len", "32",
        "-b", "8", "--epochs", "1", "--steps-per-epoch", "2",
        "--corpus-tokens", "4096", "--lr", "1e-3",
    ])
    assert len(result["history"]) == 1


def test_data_parallel_cli_plan_guards():
    """The image CLI's --plan accepts only the degenerate data-axis
    specs (dpN / fsdpN): pp/sp/ep specs, engine conflicts, and
    wrong-sized data axes are refused with the plan field named."""
    with pytest.raises(SystemExit, match="data axis only"):
        data_parallel.main([
            "--plan", "pp2xdp4", "--model", "tinycnn",
            "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit, match="data axis only"):
        data_parallel.main([
            "--plan", "sp2xdp4", "--model", "tinycnn",
            "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit, match="conflicts with --engine"):
        data_parallel.main([
            "--plan", "fsdp8", "--engine", "ddp",
            "--model", "tinycnn", "-type", "Synthetic",
        ])
    with pytest.raises(SystemExit, match="respell"):
        data_parallel.main([
            "--plan", "dp64", "--model", "tinycnn",
            "-type", "Synthetic",
        ])


@pytest.mark.slow
def test_data_parallel_cli_plan_fsdp_bucketed(tmp_path, monkeypatch):
    """A now-legal combo (ISSUE 19 satellite): `--plan fsdp8` spells
    --engine fsdp, and the reducer knobs compose with it — the
    degenerate plan rides the existing engine's full knob surface.
    `slow` (tier-1 budget); tier-1 twins:
    test_data_parallel_cli_plan_guards (the --plan mapping + refusal
    surface on this CLI) + the existing fsdp bucketed-reducer CLI
    runs."""
    monkeypatch.chdir(tmp_path)
    result = data_parallel.main([
        "--plan", "fsdp8", "--model", "tinycnn",
        "--grad-reduction", "bucketed", "--bucket-mb", "0.25",
        "-type", "Synthetic", "-b", "64", "--val-batch-size", "128",
        "--epochs", "1", "--steps-per-epoch", "2",
    ])
    assert len(result["history"]) == 1
