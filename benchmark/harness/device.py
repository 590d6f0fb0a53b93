"""The device a run is on: refuse the wrong one, describe the right one,
count what compiles."""

from __future__ import annotations

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """JAX does not see the chips the cell is defined on."""


def describe(chips: int, rehearsal: bool) -> dict:
    """Platform, kind and count as JAX reports them. Outside a rehearsal
    the platform has to be a TPU and the count exactly the cell's chips:
    on more, `cli.lm`'s default mesh would spread a one-chip cell over
    all of them and measure another cell under this one's name."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearsal:
        if info["count"] < chips:
            raise NoAccelerator(
                f"rehearsal needs {chips} virtual devices, JAX has "
                f"{info['count']}"
            )
        return info
    if info["platform"] != "tpu":
        raise NoAccelerator(
            f"no accelerator: JAX found platform {info['platform']!r} "
            f"({info['count']} device(s)); cells run on a TPU only "
            "(--rehearsal runs toy widths on the CPU)"
        )
    if info["count"] != chips:
        raise NoAccelerator(
            f"this cell is defined on {chips} chip(s); JAX sees "
            f"{info['count']}"
        )
    return info


def seed_key(seed: int):
    """The key the weights are made from. The `unsafe_rbg` generator is
    one device operation per array where the default threefry is a few
    hundred: GPT-2 XL's 580 arrays compile in a quarter of the time and
    fill in seconds (its statistical weaknesses do not matter to random
    weights)."""
    import jax

    return jax.random.key(seed, impl="unsafe_rbg")


def memory_peak_bytes() -> int:
    """Peak bytes of the fullest device's memory that were taken: the
    buffers' peak (`peak_bytes_in_use`: weights, caches, state, results)
    plus the peak reservation for compiled programs' temporaries
    (`peak_bytes_reserved`), which the v5e's runtime keeps apart and
    which is most of a step's footprint (GPT-2 small's train step: 2.1 GB
    of buffers, 9 GB of temporaries). 0 where the backend reports
    neither, as the CPU does."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def memory_stats_of_first() -> dict:
    """The first device's memory counters, for the run's info line."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    keys = ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")
    return {k: stats[k] for k in keys if k in stats}


class CompileCounter:
    """Counts programs JAX instantiates (compiled or loaded from the
    persistent cache) between `start()` and `stop()`: the measured
    window has to see none — every shape is warmed before it."""

    def __init__(self):
        self.count = 0
        self._on = False

    def _listen(self, event: str, _duration: float, **_kw) -> None:
        if self._on and event == BACKEND_COMPILE_EVENT:
            self.count += 1

    def start(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True

    def stop(self) -> int:
        import jax.monitoring

        self._on = False
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return self.count
