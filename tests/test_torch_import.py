"""MobileNetV2 torch-checkpoint transplant tests.

Ground truth is torch itself: a functional interpreter drives
`torch.nn.functional` ops straight off the state_dict tensors (no
nn.Module graph), executing the reference model's documented op sequence
(relu(bn1(conv1)) -> blocks -> bn2(conv2) -> relu -> avgpool4 -> flatten
-> linear, residual add when stride==1 — `mobilenetv2.py:10-77`). The
transplanted JAX model must reproduce its logits to float tolerance.
"""

import jax
import numpy as np
import pytest

from distributed_model_parallel_tpu.models import layers as L
from distributed_model_parallel_tpu.models.mobilenetv2 import (
    CFG,
    mobilenet_v2,
)
from distributed_model_parallel_tpu.models.torch_import import (
    mobilenetv2_from_torch_state_dict,
    normalize_state_dict,
)

torch = pytest.importorskip("torch")
import torch.nn.functional as F  # noqa: E402


def make_state_dict(num_classes=10, seed=0):
    """A reference-schema MobileNetV2 state_dict with random values —
    shapes derived independently from the CFG table (so a transplant bug
    cannot cancel against a generation bug)."""
    rng = np.random.RandomState(seed)

    def conv(o, i, k):
        return rng.randn(o, i, k, k).astype(np.float32) * 0.1

    def bn(n, prefix, sd):
        sd[f"{prefix}.weight"] = rng.rand(n).astype(np.float32) + 0.5
        sd[f"{prefix}.bias"] = rng.randn(n).astype(np.float32) * 0.1
        sd[f"{prefix}.running_mean"] = rng.randn(n).astype(np.float32) * 0.1
        sd[f"{prefix}.running_var"] = rng.rand(n).astype(np.float32) + 0.5
        sd[f"{prefix}.num_batches_tracked"] = np.int64(7)

    sd = {}
    sd["conv1.weight"] = conv(32, 3, 3)
    bn(32, "bn1", sd)
    in_planes = 32
    i = 0
    for expansion, out_planes, num_blocks, stride in CFG:
        for s in [stride] + [1] * (num_blocks - 1):
            planes = expansion * in_planes
            sd[f"layers.{i}.conv1.weight"] = conv(planes, in_planes, 1)
            bn(planes, f"layers.{i}.bn1", sd)
            sd[f"layers.{i}.conv2.weight"] = conv(planes, 1, 3)  # depthwise
            bn(planes, f"layers.{i}.bn2", sd)
            sd[f"layers.{i}.conv3.weight"] = conv(out_planes, planes, 1)
            bn(out_planes, f"layers.{i}.bn3", sd)
            if s == 1 and in_planes != out_planes:
                sd[f"layers.{i}.shortcut.0.weight"] = conv(
                    out_planes, in_planes, 1
                )
                bn(out_planes, f"layers.{i}.shortcut.1", sd)
            in_planes = out_planes
            i += 1
    sd["conv2.weight"] = conv(1280, 320, 1)
    bn(1280, "bn2", sd)
    sd["linear.weight"] = rng.randn(num_classes, 1280).astype(np.float32) * 0.1
    sd["linear.bias"] = rng.randn(num_classes).astype(np.float32) * 0.1
    return sd


def torch_forward(sd, x_nchw):
    """Functional-torch ground truth (eval mode)."""
    t = {k: torch.tensor(v) for k, v in sd.items()
         if not k.endswith("num_batches_tracked")}

    def bn(x, p):
        return F.batch_norm(
            x, t[f"{p}.running_mean"], t[f"{p}.running_var"],
            t[f"{p}.weight"], t[f"{p}.bias"], False, 0.1, 1e-5,
        )

    x = torch.tensor(x_nchw)
    x = F.relu(bn(F.conv2d(x, t["conv1.weight"], padding=1), "bn1"))
    in_planes = 32
    i = 0
    for expansion, out_planes, num_blocks, stride in CFG:
        for s in [stride] + [1] * (num_blocks - 1):
            p = f"layers.{i}"
            y = F.relu(bn(F.conv2d(x, t[f"{p}.conv1.weight"]), f"{p}.bn1"))
            y = F.relu(bn(
                F.conv2d(y, t[f"{p}.conv2.weight"], stride=s, padding=1,
                         groups=y.shape[1]),
                f"{p}.bn2",
            ))
            y = bn(F.conv2d(y, t[f"{p}.conv3.weight"]), f"{p}.bn3")
            if s == 1:
                if in_planes != out_planes:
                    sc = bn(
                        F.conv2d(x, t[f"{p}.shortcut.0.weight"]),
                        f"{p}.shortcut.1",
                    )
                else:
                    sc = x
                y = y + sc
            x = y
            in_planes = out_planes
            i += 1
    x = F.relu(bn(F.conv2d(x, t["conv2.weight"]), "bn2"))
    x = F.avg_pool2d(x, 4).flatten(1)
    return (x @ t["linear.weight"].T + t["linear.bias"]).numpy()


def test_transplant_logits_match_torch():
    sd = make_state_dict()
    model = mobilenet_v2(10)
    params, state = model.init(jax.random.PRNGKey(0))
    params, state = mobilenetv2_from_torch_state_dict(params, state, sd)

    rng = np.random.RandomState(3)
    x = rng.rand(4, 32, 32, 3).astype(np.float32)
    want = torch_forward(sd, np.transpose(x, (0, 3, 1, 2)))
    got, _ = model.apply(params, state, x, L.Context(train=False))
    np.testing.assert_allclose(
        np.asarray(got), want, rtol=5e-4, atol=5e-4
    )


def test_reference_checkpoint_wrapper_and_dataparallel_prefix():
    """The reference saves {'net': sd, 'acc', 'epoch'} with 'module.*'
    keys (`data_parallel.py:77,146-151`); both unwrap transparently."""
    sd = make_state_dict()
    wrapped = {
        "net": {f"module.{k}": v for k, v in sd.items()},
        "acc": 93.8,
        "epoch": 41,
    }
    flat = normalize_state_dict(wrapped)
    assert set(flat) == set(sd)
    model = mobilenet_v2(10)
    params, state = model.init(jax.random.PRNGKey(0))
    p1, s1 = mobilenetv2_from_torch_state_dict(params, state, wrapped)
    p2, s2 = mobilenetv2_from_torch_state_dict(params, state, sd)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_array_equal(a, b)


def test_head_mismatch_finetunes_fresh_classifier():
    """ImageNet-head checkpoints (1000 classes) keep the fresh 10-class
    classifier — the reference's finetune-to-CIFAR path."""
    sd = make_state_dict(num_classes=1000)
    model = mobilenet_v2(10)
    params, state = model.init(jax.random.PRNGKey(0))
    p, s = mobilenetv2_from_torch_state_dict(params, state, sd)
    assert p["head"]["linear"]["w"].shape == (1280, 10)
    np.testing.assert_array_equal(
        p["head"]["linear"]["w"], np.asarray(params["head"]["linear"]["w"])
    )
    with pytest.raises(ValueError, match="classes"):
        mobilenetv2_from_torch_state_dict(
            params, state, sd, allow_head_mismatch=False
        )


def test_unknown_keys_fail_loudly():
    sd = make_state_dict()
    sd["layers.3.mystery.weight"] = np.zeros((1,), np.float32)
    model = mobilenet_v2(10)
    params, state = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="not consumed"):
        mobilenetv2_from_torch_state_dict(params, state, sd)


def test_missing_keys_fail_loudly():
    sd = make_state_dict()
    del sd["layers.5.conv2.weight"]
    model = mobilenet_v2(10)
    params, state = model.init(jax.random.PRNGKey(0))
    with pytest.raises(KeyError, match="layers.5.conv2.weight"):
        mobilenetv2_from_torch_state_dict(params, state, sd)


@pytest.mark.slow
def test_cli_finetune_flag(tmp_path, monkeypatch):
    """End-to-end: --finetune loads a reference-format checkpoint into
    the DP training entry point and trains from it. Slow (full
    MobileNetV2 train-step compile on the CPU mesh); the transplant
    numerics and the head-swap logic have fast twins above."""
    sd = make_state_dict(num_classes=1000)  # ImageNet-style head
    np.savez(tmp_path / "pre.npz", **sd)
    monkeypatch.chdir(tmp_path)

    from distributed_model_parallel_tpu.cli.data_parallel import main

    res = main([
        "--dataset-type", "Synthetic", "--data", str(tmp_path),
        "--epochs", "1", "--steps-per-epoch", "2", "-b", "16",
        "--val-batch-size", "16", "--lr", "0.001",
        "--finetune", str(tmp_path / "pre.npz"),
        "--log-file", "ft.txt",
    ])
    assert len(res["history"]) == 1


def test_export_roundtrip_bit_exact(tmp_path):
    """The inverse bridge: export a JAX MobileNetV2 to the reference's
    torch schema, save with the reference's {'net': module.*} wrapper,
    re-import — every leaf bit-exact, no leftover/missing keys."""
    from distributed_model_parallel_tpu.models.torch_import import (
        load_torch_checkpoint,
        save_reference_checkpoint,
    )

    model = mobilenet_v2(10)
    params, state = model.init(jax.random.PRNGKey(3))
    path = str(tmp_path / "export.pth")
    save_reference_checkpoint(path, params, state, acc=93.8, epoch=17)

    ckpt = load_torch_checkpoint(path)
    p2, s2 = mobilenetv2_from_torch_state_dict(params, state, ckpt)
    for (path_a, a), b in zip(
        jax.tree_util.tree_leaves_with_path(
            jax.tree_util.tree_map(np.asarray, params)
        ),
        jax.tree_util.tree_leaves(p2),
    ):
        np.testing.assert_array_equal(
            a, b, err_msg=jax.tree_util.keystr(path_a)
        )
    for a, b in zip(
        jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(np.asarray, state)
        ),
        jax.tree_util.tree_leaves(s2),
    ):
        np.testing.assert_array_equal(a, b)
