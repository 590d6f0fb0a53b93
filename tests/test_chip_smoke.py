"""chip_smoke.py and the start-up code it guards: the no-chip refusal,
the CPU rehearsal of every leg, the peak table its MFU divides by, where
the compile cache goes, and a single host starting without a rendezvous.
"""

import os
import resource
import shutil
import subprocess
import sys

import jax
import pytest

import chip_smoke
from distributed_model_parallel_tpu.runtime import dist, platform

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TAG = "[platform: cpu, rehearsal] "


def test_without_the_flag_on_cpu_refuses_before_any_work(tmp_path, capsys):
    """No TPU and no rehearsal flag: non-zero, the missing chip named on
    stderr, nothing on stdout (no result), nothing written."""
    out = tmp_path / "out"
    assert chip_smoke.main(["--out", str(out)]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "no accelerator" in captured.err and "'cpu'" in captured.err
    assert not out.exists()


def test_alone_in_a_directory_fails_without_a_result(tmp_path):
    """The script with nothing else of the repo beside it: non-zero and
    no result line, even when asked for the rehearsal."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    res = subprocess.run(
        [sys.executable, "chip_smoke.py", "--cpu-rehearsal"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "not importable" in res.stderr


@pytest.fixture
def small_files_only():
    """Hold the process to 256 KiB per file, as a machine that checks
    the smoke may (the driver's refused a full-width snapshot with
    EFBIG, PR 21). The toy model's checkpoint is ~1.5 MB."""
    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    resource.setrlimit(resource.RLIMIT_FSIZE, (256 * 1024, hard))
    yield
    resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))


def test_rehearsal_runs_every_leg_and_labels_every_line(
    tmp_path, capsys, small_files_only
):
    """--cpu-rehearsal drives every leg's code path at toy widths on the
    CPU mesh (flash kernels interpreted; with 8 devices the four-chip
    legs run too), labels every line, prints no result, writes only
    under --out and no large file there (no checkpoint)."""
    before = set(os.listdir(REPO))
    assert chip_smoke.main(["--cpu-rehearsal", "--out", str(tmp_path)]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert lines and all(line.startswith(TAG) for line in lines)
    assert not any('"ok"' in line for line in lines)
    passed = {
        line[len(TAG):].split(":")[0]: line
        for line in lines if ": PASS " in line
    }
    assert list(passed) == [
        "leg barrier", "leg train[dp8]", "leg train_ring_flash[dp8]",
        "leg serve", "leg serve_int8", "leg train[pp2xdp2]",
        "leg serve[tp4]",
    ]
    # forward + two backward flash kernels per layer; four int8
    # projection sites per layer
    layers = chip_smoke.TOY.layers
    assert f"kernel_sites={3 * layers} " in passed["leg train_ring_flash[dp8]"]
    assert passed["leg serve_int8"].endswith(f"kernel_sites={4 * layers}")
    assert "devices=[0, 1, 2, 3]" in passed["leg train[pp2xdp2]"]
    assert "devices=[0, 1, 2, 3]" in passed["leg serve[tp4]"]
    assert "devices=[0] " in passed["leg serve"]  # one-chip replica
    for name in passed:
        leg_dir = tmp_path / name[len("leg "):]
        assert (leg_dir / "log.txt").exists()
        assert not (leg_dir / "checkpoint").exists()
    assert (tmp_path / "train[dp8]" / "train.txt").read_text().startswith(
        "epoch 0 train_loss"
    )
    assert set(os.listdir(REPO)) == before  # the checkout stays clean


def test_a_failing_leg_fails_the_run(tmp_path, capsys, monkeypatch):
    """A leg that raises propagates: no PASS line for it, no later leg,
    no result."""
    def broken(*args, **kwargs):
        raise RuntimeError("kernel refused")

    monkeypatch.setattr(chip_smoke, "train_leg", broken)
    with pytest.raises(RuntimeError, match="kernel refused"):
        chip_smoke.main(["--cpu-rehearsal", "--out", str(tmp_path)])
    out = capsys.readouterr().out
    assert "leg barrier: PASS" in out
    assert "leg train" not in out and "leg serve" not in out
    assert '"ok"' not in out


# ------------------------------------------------ the peak an MFU divides by


@pytest.mark.parametrize("kind, tflops", [
    ("TPU v5 lite", 197.0),  # as JAX reports a v5e (chip run, PR 21)
    ("TPU v5e", 197.0), ("TPU v5p", 459.0), ("TPU v4", 275.0),
])
def test_known_device_kinds_have_a_peak(kind, tflops):
    assert platform.peak_bf16_flops(kind) == tflops * 1e12


def test_unknown_device_kind_is_an_error():
    """An MFU against a guessed peak is worse than none: a device the
    table does not know raises, naming it."""
    with pytest.raises(ValueError, match="TPU v9 hyper"):
        platform.peak_bf16_flops("TPU v9 hyper")
    with pytest.raises(ValueError, match="cpu"):
        platform.peak_bf16_flops("cpu")


# --------------------------------------------------- compile cache placement


@pytest.fixture
def config_updates(monkeypatch):
    """Record `jax.config.update` calls instead of applying them."""
    calls = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: calls.append((name, value))
    )
    return calls


def test_cache_dir_from_the_environment_is_left_to_jax(
    tmp_path, monkeypatch, config_updates
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.enable_compile_cache() == str(tmp_path)
    assert config_updates == []  # JAX reads the variable; code sets nothing


def test_cache_dir_defaults_to_a_fixed_path_in_the_checkout(
    tmp_path, monkeypatch, config_updates
):
    """Unset: <checkout>/.jax_cache, derived from the package's location
    (the path is part of the cache key), not from the cwd."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    want = os.path.join(REPO, ".jax_cache")
    assert platform.enable_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]


# --------------------------------------------------- single-host start-up


@pytest.mark.parametrize("env, multi_host", [
    ({}, False),
    # what a single v5e host carries (chip run, PR 21)
    ({"TPU_WORKER_ID": "0", "TPU_WORKER_HOSTNAMES": "localhost"}, False),
    ({"TPU_WORKER_ID": "1", "TPU_WORKER_HOSTNAMES": "h0,h1"}, True),
    ({"TPU_WORKER_ID": "0"}, True),  # no host list: the worker id decides
    ({"CLOUD_TPU_TASK_ID": "0"}, True),
    ({"COORDINATOR_ADDRESS": "h0:1234",
      "TPU_WORKER_HOSTNAMES": "localhost"}, True),
])
def test_rendezvous_only_where_the_environment_names_peers(
    env, multi_host, monkeypatch
):
    for name in ("COORDINATOR_ADDRESS", "CLOUD_TPU_TASK_ID",
                 "TPU_WORKER_ID", "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(name, raising=False)
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    assert dist._multi_host_env() is multi_host
    calls = []
    monkeypatch.setattr(dist, "_initialized", False)
    monkeypatch.setattr(
        jax.distributed, "initialize", lambda **kw: calls.append(kw)
    )
    monkeypatch.setattr(dist.log, "info", lambda *a, **k: None)
    dist.initialize_backend()
    assert len(calls) == int(multi_host)
