"""The program's host spans (`observability/trace.py`) and the
profiler, as the drivers use them in a `--trace 1` run."""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import threading
import time
from typing import List, Optional

from benchmark.harness.stats import percentile
from benchmark.harness.trace_reduce import (
    SYNC_PREFIX,
    Event,
    find_xplane,
    read_xplane,
    reduce_trace,
)

ORIGIN_MARK = "bench_origin"
# Named tracks (>= 1000) hold one request's queued/prefill/decode legs;
# the host loop's own spans are on the thread tracks below them.
FIRST_NAMED_TRACK = 1000


class HostSpans:
    """Switches the program's tracer on and returns its loop spans on
    the `time.perf_counter` clock."""

    def __init__(self):
        from distributed_model_parallel_tpu.observability import trace

        self._tracer = trace.enable()
        self._tracer.clear()
        # Span timestamps are relative to the tracer's origin; one
        # marker with both readings recovers it.
        self._tracer.instant(ORIGIN_MARK)
        self._anchor = self._tracer.now()

    def collect(self) -> List[Event]:
        from distributed_model_parallel_tpu.observability import trace

        trace.disable()
        events = self._tracer.to_chrome()["traceEvents"]
        self._tracer.clear()
        mark = next(e["ts"] for e in events if e["name"] == ORIGIN_MARK)
        origin = self._anchor - mark * 1e-6
        return [
            Event(e["name"], origin + e["ts"] * 1e-6, e["dur"] * 1e-6)
            for e in events
            if e.get("ph") == "X" and e["tid"] < FIRST_NAMED_TRACK
        ]


def _start_profiler(trace_dir: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    # Python-level call tracing slows the host loop it would measure
    # and makes traces of a few seconds too large to keep.
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(
        f"{SYNC_PREFIX}{time.perf_counter_ns()}"
    ):
        pass


def _stop_profiler() -> None:
    import jax

    jax.profiler.stop_trace()


@contextlib.contextmanager
def profiled(trace_dir: str):
    """Device trace of the enclosed block, taken on this thread."""
    _start_profiler(trace_dir)
    try:
        yield
    finally:
        _stop_profiler()


class SliceProfiler(threading.Thread):
    """Device trace of `length_s` seconds that begins `after_s` seconds
    from `start()`, taken from a helper thread while the main thread is
    inside the program's loop (which cannot be paused from outside)."""

    def __init__(self, trace_dir: str, after_s: float, length_s: float):
        super().__init__(name="bench-slice-profiler", daemon=True)
        self.trace_dir = trace_dir
        self.after_s = after_s
        self.length_s = length_s
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            time.sleep(self.after_s)
            _start_profiler(self.trace_dir)
            try:
                time.sleep(self.length_s)
            finally:
                _stop_profiler()
        except BaseException as e:  # noqa: BLE001 — re-raised by finish()
            self.error = e

    def finish(self, timeout: float = 300.0) -> None:
        self.join(timeout)
        if self.is_alive():
            raise RuntimeError("the profiler thread did not stop")
        if self.error is not None:
            raise self.error


@contextlib.contextmanager
def trace_dir():
    """A directory for one trace under TMPDIR, removed afterwards: the
    numbers are kept, the file is not."""
    path = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        yield path
    finally:
        shutil.rmtree(path, ignore_errors=True)


def reduce_dir(path: str, host_spans: List[Event]) -> dict:
    """The trace under `path` and the host spans (perf_counter clock)
    -> the reduced numbers, spans moved onto the profiler's clock."""
    x = read_xplane(find_xplane(path))
    shifted = (
        [Event(s.name, s.start - x.offset, s.dur) for s in host_spans]
        if x.offset is not None else []
    )
    out = reduce_trace(x.devices, shifted, x.async_ops)
    out["clock_synced"] = x.offset is not None
    # Device time of the compiled programs, by the program's own names:
    # the total, and the median run (a run cut by the edge of the traced
    # slice does not move a median).
    out["program_seconds"] = {k: sum(v) for k, v in x.programs.items()}
    out["program_median_s"] = {
        k: percentile(v, 50) for k, v in x.programs.items()
    }
    return out
