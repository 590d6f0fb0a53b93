"""Host-side span tracer — nested spans + counters, Chrome trace export.

hlolint pins what the compiled program asks the network for (STATIC);
this module is the RUNTIME half: what the host loops actually spent their time on.
PyTorch's DDP is explained in the paper through its bucketed Reducer
*timeline* — this is the instrument that lets our loops draw the same
picture (Trainer phases, serving admission→prefill→decode→eviction,
checkpoint snapshot vs background write).

Design constraints, in priority order:

* **Zero-cost off-path.** Tracing is DISABLED by default; a disabled
  call site pays one attribute load + one branch and allocates nothing
  (`span()` returns a shared no-op context manager, `counter()` returns
  immediately). Safe to leave permanently wired into hot host loops.
* **Thread-safe.** The checkpoint writer thread and the main loop
  record concurrently; one lock around the event list. (Device-side
  time is NOT measured here — JAX dispatch is async; spans time the
  HOST, and the Trainer's value-fetch fences are themselves spans, so
  the device time shows up as the `sync` phase. `jax.profiler` remains
  the device-side tool.)
* **Deterministic under test.** The clock is injected
  (`Tracer(clock=...)`); nothing in the export depends on wall time,
  thread ids map to small first-seen ordinals, and insertion order is
  preserved — a fake clock yields a byte-stable golden file.

Export is the Chrome `trace_event` JSON format (one object with a
`traceEvents` list), loadable in `chrome://tracing` / Perfetto:
complete events (`"ph": "X"`) with microsecond `ts`/`dur` nest by
containment per track, counters are `"ph": "C"`. `ts` is relative to
the tracer's origin (its construction instant).

Enablement: the module-global tracer (`get_tracer()`) starts enabled
when the environment carries ``DMP_TRACE=1`` (or any non-empty value
other than ``0``/``false``); programs opt in explicitly with
`enable()` (e.g. `cli/serve.py --trace-out`).

No jax, no numpy: importable everywhere, including the jax-free
analysis layer and the writer thread.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional


class _NullSpan:
    """Shared no-op context manager — the disabled path's entire cost
    is returning this singleton."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records its own start on __enter__ and appends
    the complete event on __exit__ (so nested spans land innermost-
    first, which the Chrome viewer handles; ordering in the export is
    insertion order)."""

    __slots__ = ("_tracer", "name", "args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0.0

    def __enter__(self) -> "_Span":
        self._t0 = self._tracer._now()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = self._tracer._now()
        self._tracer._append_complete(
            self.name, self._t0, t1 - self._t0, None, self.args
        )
        return False


class Tracer:
    """Nested spans + counters with Chrome `trace_event` export
    (module docstring). All public mutators are thread-safe."""

    def __init__(self, clock: Optional[Callable[[], float]] = None,
                 enabled: bool = False):
        self._clock = clock if clock is not None else time.perf_counter
        self.enabled = enabled
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._tids: Dict[Any, int] = {}  # thread ident -> ordinal
        self._tracks: Dict[str, int] = {}  # named track -> ordinal
        self._origin = self._clock()

    # ------------------------------------------------------- recording

    def _now(self) -> float:
        return self._clock() - self._origin

    def now(self) -> float:
        """An absolute timestamp in THIS tracer's clock domain — the
        domain `complete()` expects. Producers that record timestamps
        for later emission (the serving scheduler's per-request legs)
        must take them from here, not `time.perf_counter()`, so an
        injected clock keeps span and report timings coherent. Works
        with tracing disabled (it is also the report clock)."""
        return self._clock()

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tids.get(ident)
                if tid is None:
                    tid = len(self._tids)
                    self._tids[ident] = tid
        return tid

    def track_id(self, name: str) -> int:
        """Stable integer track (Chrome `tid`) for a NAMED timeline —
        e.g. one per serving request — disjoint from thread tracks
        (offset by 1000)."""
        with self._lock:
            tid = self._tracks.get(name)
            if tid is None:
                tid = 1000 + len(self._tracks)
                self._tracks[name] = tid
                self._events.append({
                    "name": "thread_name", "ph": "M", "pid": 0,
                    "tid": tid, "args": {"name": name},
                })
            return tid

    def _append_complete(self, name: str, t0: float, dur: float,
                         tid: Optional[int], args: dict) -> None:
        ev = {
            "name": name,
            "ph": "X",
            "ts": round(t0 * 1e6, 3),
            "dur": round(dur * 1e6, 3),
            "pid": 0,
            "tid": self._tid() if tid is None else tid,
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def span(self, name: str, **args) -> Any:
        """Context manager timing one nested host-side phase. The
        disabled path is one branch + a shared singleton."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args)

    def complete(self, name: str, start: float, end: float,
                 tid: Optional[int] = None, **args) -> None:
        """Record a complete event from timestamps ALREADY taken in the
        tracer's clock domain — i.e. values of `now()` (the scheduler's
        per-request legs, emitted once at eviction when all legs are
        known)."""
        if not self.enabled:
            return
        self._append_complete(
            name, start - self._origin, end - start, tid, args
        )

    def counter(self, name: str, value) -> None:
        """One sample of a named counter series (Chrome `"ph": "C"`)."""
        if not self.enabled:
            return
        ev = {
            "name": name, "ph": "C", "ts": round(self._now() * 1e6, 3),
            "pid": 0, "args": {name: value},
        }
        with self._lock:
            self._events.append(ev)

    def instant(self, name: str, **args) -> None:
        """Zero-duration marker (Chrome `"ph": "i"`)."""
        if not self.enabled:
            return
        ev = {
            "name": name, "ph": "i", "s": "t",
            "ts": round(self._now() * 1e6, 3),
            "pid": 0, "tid": self._tid(),
        }
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    # --------------------------------------------------------- export

    def to_chrome(self) -> dict:
        """The Chrome `trace_event` object — round-trips `json.loads`."""
        with self._lock:
            events = list(self._events)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON; returns the path."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1)
        return path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._tracks.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


# ------------------------------------------------------ global tracer

_ENV_FLAG = "DMP_TRACE"
_global_tracer: Optional[Tracer] = None
_global_lock = threading.Lock()


def _env_enabled() -> bool:
    v = os.environ.get(_ENV_FLAG, "").strip().lower()
    return v not in ("", "0", "false", "off")


def get_tracer() -> Tracer:
    """The process-wide tracer every wired layer records to. Created on
    first use; starts enabled iff DMP_TRACE is set."""
    global _global_tracer
    t = _global_tracer
    if t is None:
        with _global_lock:
            t = _global_tracer
            if t is None:
                t = Tracer(enabled=_env_enabled())
                _global_tracer = t
    return t


def set_tracer(tracer: Optional[Tracer]) -> None:
    """Swap the process-wide tracer (tests inject a deterministic-clock
    instance; None resets to the lazy default)."""
    global _global_tracer
    with _global_lock:
        _global_tracer = tracer


def enable() -> Tracer:
    t = get_tracer()
    t.enabled = True
    return t


def disable() -> None:
    get_tracer().enabled = False


__all__ = [
    "Tracer",
    "disable",
    "enable",
    "get_tracer",
    "set_tracer",
]
