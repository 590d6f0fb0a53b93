"""Backend placement shared by the entry points: the virtual CPU mesh
for structural checks, and where compiled programs are cached.

JAX reads `JAX_PLATFORMS` and `XLA_FLAGS` when it is imported and when
the first backend client starts, so `force_cpu` has to run before the
first computation; `enable_compile_cache` before the first compile.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache: the cache key includes the directory, so the
# default must be the same path on every run of the same checkout.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    ))),
    ".jax_cache",
)

# Peak bf16 matmul TFLOP/s per chip by TPU generation (public numbers);
# MFU is measured FLOP/s divided by this. A device_kind that matches no
# key is an error, never a default.
PEAK_BF16_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}


def peak_bf16_flops(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, tflops in sorted(
        PEAK_BF16_TFLOPS.items(), key=lambda kv: -len(kv[0])
    ):
        if key in kind:
            return tflops * 1e12
    raise ValueError(
        f"no bf16 peak known for device_kind {device_kind!r}; add it to "
        "PEAK_BF16_TFLOPS with its source before reporting an MFU"
    )


def force_cpu(n_devices: int = 8):
    """Force the cpu platform with >= n_devices virtual devices; returns
    the device list. Safe to call before or after `import jax`, but only
    before the CPU backend's first initialization (the virtual-device
    flag is read once, when the CPU client starts)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, found {len(devices)} "
            f"(platform {devices[0].platform}); was the CPU backend "
            "initialized before force_cpu()?"
        )
    return devices[:n_devices]


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set JAX already reads it and
    nothing is changed; otherwise the cache goes to `<checkout>/.jax_cache`.
    Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed

    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_DIR)
    return _DEFAULT_CACHE_DIR
