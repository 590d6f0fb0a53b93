"""`--rehearsal` of the training cells, in this process on the conftest's
virtual CPU devices: the whole run from manifest to last line at toy
widths. The numbers mean nothing; the line's shape is the contract's."""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.harness import manifest  # noqa: E402

DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def rehearse(capsys, workload, trace, seed=1):
    capsys.readouterr()
    rc = run.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
         "--trace", str(trace), "--rehearsal"],
        t_process=time.perf_counter(),
    )
    lines = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(lines[-1]), json.loads(lines[-2])["info"]


def check_line(line, workload, trace):
    cell = manifest.resolve_cell(manifest.load_manifest(), workload)
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line) == (keys | {"breakdown"} if trace else keys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    listed = {x["name"]: x for x in (cell.per_layer if trace else cell.end_to_end)}
    assert set(line["metrics"]) <= set(listed)
    for name, value in line["metrics"].items():
        assert set(value) == {"value", "unit"}
        assert value["unit"] == listed[name]["unit"]
        assert isinstance(value["value"], float)
    if trace:
        assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        for rows in line["breakdown"].values():
            assert len(rows) <= 10
            assert all(isinstance(n, str) and s >= 0 for n, s in rows)
        assert line["breakdown"]["device_ops"]
    else:
        assert set(line["device"]) == DEVICE_KEYS
        # every end-to-end metric of the cell, none of them 0
        assert set(line["metrics"]) == set(listed)
        assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("workload,trace", [
    ("gpt2s_train", 0), ("gpt2s_train", 1),
    ("gpt2xl_train_fsdp4", 0), ("gpt2xl_train_fsdp4", 1),
])
def test_training_cell_rehearsal_prints_the_contracts_line(capsys, workload, trace):
    from distributed_model_parallel_tpu.cli import lm
    from distributed_model_parallel_tpu.training.trainer import Trainer

    line, info = rehearse(capsys, workload, trace)
    check_line(line, workload, trace)
    assert lm.Trainer is Trainer  # the substitute is gone again
    assert info["compiles_in_window"] == 0
    check = info["check"]
    assert abs(check["step0_loss"] - check["reference_loss"]) < 1e-3
    assert check["epoch_losses"][-1] < check["epoch_losses"][0]
    assert line["attempted"] % 2 == 0 and line["attempted"] >= 4
    if trace:
        assert "train_data_share" in line["metrics"]
        assert "train_device_idle_share" in line["metrics"]


def test_no_result_without_the_cells_chips(capsys, monkeypatch):
    """Outside a rehearsal the CPU is refused: exit 2, no last line."""
    capsys.readouterr()
    rc = run.main(["--workload", "gpt2s_train", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out.strip() == ""
    assert "no accelerator" in out.err
