"""bench.py's contract with the device: no chip -> no metric line and a
non-zero exit; an unknown chip -> an error, not `mfu: null`; every mode
runs in the one process that holds the chip, and a mode that fails
fails the run.
"""

import os
import subprocess
import sys

import pytest

import bench

BENCH = os.path.abspath(bench.__file__)


def _run_bench(*args):
    return subprocess.run(
        [sys.executable, BENCH, *args], capture_output=True, text=True,
        timeout=240, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


def test_no_chip_exits_nonzero_with_one_line_and_no_metric():
    """`JAX_PLATFORMS=cpu python bench.py`: one line on stderr saying
    there is no accelerator, nothing that looks like a result."""
    res = _run_bench()
    assert res.returncode != 0
    assert '"metric"' not in res.stdout and res.stdout.strip() == ""
    (line,) = res.stderr.strip().splitlines()
    assert "no accelerator" in line and bench.METRIC in line


def test_headline_refuses_cpu_before_measuring(monkeypatch):
    """The refusal comes before any model is built or compiled."""
    def must_not_run(*args, **kwargs):
        raise AssertionError("measured on a CPU")

    monkeypatch.setattr(bench, "_measure", must_not_run)
    with pytest.raises(bench.NoAcceleratorError, match="cpu device"):
        bench.run_headline()


@pytest.mark.parametrize("kind, tflops", [
    ("TPU v5 lite", 197.0),  # as JAX reports a v5e (chip run, PR 21)
    ("TPU v5e", 197.0), ("TPU v5p", 459.0), ("TPU v4", 275.0),
])
def test_known_device_kinds_have_a_peak(kind, tflops):
    assert bench.peak_bf16_flops(kind) == tflops * 1e12


def test_unknown_device_kind_is_an_error():
    """An MFU against a guessed peak is worse than none: a device the
    table does not know raises, naming it."""
    with pytest.raises(ValueError, match="TPU v9 hyper"):
        bench.peak_bf16_flops("TPU v9 hyper")
    with pytest.raises(ValueError, match="cpu"):
        bench.peak_bf16_flops("cpu")


def test_aot_step_lets_a_failed_compile_raise():
    """No carry-on with `flops=None`: if the step cannot be lowered and
    compiled, the measurement fails."""
    class Refuses:
        def lower(self, *args):
            raise RuntimeError("Mosaic refused the kernel")

    class Engine:
        train_step = Refuses()

    with pytest.raises(RuntimeError, match="Mosaic refused"):
        bench._aot_step(Engine(), None, None, None, None)


def test_failed_mode_exits_nonzero_without_a_metric_line():
    """A sweep that raises (here: a refused argument) ends the process
    non-zero; no `value 0.0` line stands in for the table."""
    res = _run_bench("--scaling", "--max-devices", "0")
    assert res.returncode != 0
    assert "--max-devices must be >= 1" in res.stderr
    assert '"metric"' not in res.stdout


def test_failed_mode_keeps_its_finished_legs_and_propagates(
    monkeypatch, capsys
):
    """Legs a sweep printed before it died stay on stdout as what they
    are (partial lines); the failure itself propagates out of main()."""
    def dies_mid_sweep(max_devices, model_name, platform):
        print('{"leg": {"chips": 1}, "partial": true}', flush=True)
        raise RuntimeError("device lost")

    monkeypatch.setattr(bench, "run_scaling", dies_mid_sweep)
    with pytest.raises(RuntimeError, match="device lost"):
        bench.main(["--scaling"])
    out = capsys.readouterr().out
    assert '"partial": true' in out and '"metric"' not in out


MODES = {
    "--scaling": ("run_scaling", (4, "tinycnn", "cpu")),
    "--cm-microbench": ("run_cm", (4, "cpu", None)),
    "--reducer-microbench": ("run_reducer", (4, "cpu", None)),
    "--moe-microbench": ("run_moe", (4, "cpu", None)),
    "--plan-microbench": ("run_plan_bench", (4, "cpu", None)),
    "--serving-microbench": ("run_serving", (4, "cpu")),
    "--checkpoint-microbench": ("run_checkpoint", (4, "cpu")),
}


@pytest.mark.parametrize("flag", sorted(MODES))
def test_every_mode_runs_in_this_process(flag, monkeypatch):
    """One process holds the chip, so no mode spawns a child: each flag
    calls its function here, with the device arguments."""
    calls = []
    for name, _ in MODES.values():
        monkeypatch.setattr(
            bench, name,
            lambda *args, _name=name: calls.append((_name, args)),
        )
    monkeypatch.setattr(
        subprocess, "Popen",
        lambda *a, **k: pytest.fail("a mode started a child process"),
    )
    assert bench.main([flag, "--max-devices", "4"]) == 0
    assert calls == [MODES[flag]]


def test_parser_has_the_modes_and_no_child_flags(capsys):
    help_text = bench.build_parser().format_help()
    for flag in MODES:
        assert flag in help_text
    assert "--child" not in help_text
    with pytest.raises(SystemExit) as e:
        bench.main(["--scaling", "--reducer-microbench"])
    assert e.value.code == 2
    assert "mutually exclusive" in capsys.readouterr().err


def test_plan_flag_needs_a_sweep_and_a_file(tmp_path, capsys):
    with pytest.raises(SystemExit):
        bench.main(["--plan", str(tmp_path / "plan.json")])
    assert "--plan adds a tuned row" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        bench.main(["--cm-microbench", "--plan",
                    str(tmp_path / "missing.json")])
    assert "no such file" in capsys.readouterr().err


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-v"]))
