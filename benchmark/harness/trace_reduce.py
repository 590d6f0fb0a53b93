"""From a profiler trace to numbers: device busy time, time by
operation, idle gaps and what the host was doing in each.

The arithmetic works on plain `Event`s so the tests feed it synthetic
traces; `read_xplane` is the only part that touches the `.xplane.pb`
(through `jax.profiler.ProfileData`, nothing else).

Clocks. Device events carry the profiler's clock; the program's host
spans (`observability/trace.py`) carry `time.perf_counter`. The
benchmark writes one `bench_clock_sync#<perf_counter_ns>` annotation
into the trace, and the difference between its two timestamps puts
both on one clock.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SYNC_PREFIX = "bench_clock_sync#"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"          # one operation at a time, nested by loops
ASYNC_LINE = "Async XLA Ops"  # each asynchronous operation, start to done
MODULES_LINE = "XLA Modules"  # each run of a compiled program
# On the v5e an operation's event name is its whole HLO instruction,
# `%copy.315 = bf16[12,2048,16,12,64]{...} copy(...)`: the instruction's
# name and its (first) result shape are what is kept of it.
HLO_INSTRUCTION = re.compile(
    r"^%(?P<name>[^ ]+) = \(?(?P<shape>[a-z0-9]+\[[0-9,]*\])?"
    r"(?:.*?[ )](?P<opcode>[a-z][a-z-]*)\()?"
)
# A Mosaic (Pallas) kernel is a `custom-call` instruction in the step.
KERNEL_OPCODE = "custom-call"
# On the CPU backend (rehearsals only) XLA's operations run on these
# host threads and there is no device plane.
CPU_OPS_LINE = re.compile(r"^tf_XLAPjRtCpuClient/")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)(-start|-done)?(\.|$)"
)
NO_SPAN = "(between spans)"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds
    dur: float    # seconds
    shape: str = ""   # result shape of a device operation, where known
    opcode: str = ""  # its HLO opcode (`copy`, `fusion`, `custom-call`)

    @property
    def end(self) -> float:
        return self.start + self.dur


def device_event(name: str, start: float, dur: float) -> Event:
    """An operation's event under its instruction name (`copy.315`),
    with its result shape beside it."""
    m = HLO_INSTRUCTION.match(name)
    if not m:
        return Event(name, start, dur)
    return Event(m.group("name"), start, dur, m.group("shape") or "",
                 m.group("opcode") or "")


def merge_intervals(
    intervals: Iterable[Tuple[float, float]]
) -> List[Tuple[float, float]]:
    """Union of (start, end) intervals as a sorted disjoint list."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_seconds(events: Sequence[Event]) -> float:
    return sum(
        b - a for a, b in merge_intervals((e.start, e.end) for e in events)
    )


def op_group(name: str) -> str:
    """`fusion.123` -> `fusion`: the operation without its instance
    number, so that the layers of a model add up under one name."""
    return re.sub(r"[.\d]+$", "", name) or name


def label(e: Event) -> str:
    """`copy bf16[12,2048,16,12,64]`: what an operation is and how much
    it touches, which is how a whole-pool copy is told from a small one."""
    return f"{op_group(e.name)} {e.shape}".strip()


def self_seconds(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its own seconds: its time less the events nested
    inside it (a `while` holds its body's operations), so the sum is the
    busy time of a line that runs one thing at a time."""
    own: List[List] = []   # [event, self seconds], in start order
    stack: List[List] = []
    for e in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        if stack:
            parent = stack[-1]
            parent[1] -= min(e.dur, parent[0].end - e.start)
        entry = [e, e.dur]
        own.append(entry)
        stack.append(entry)
    return [(e, s) for e, s in own]


def self_times(events: Sequence[Event], key=lambda e: e.name
               ) -> Dict[str, float]:
    """Own seconds (see `self_seconds`) summed by `key` of the event:
    its name, or `label` to add the layers of a model up."""
    out: Dict[str, float] = {}
    for e, s in self_seconds(events):
        out[key(e)] = out.get(key(e), 0.0) + s
    return out


def top(times: Dict[str, float], n: int = 10) -> List[List]:
    return [
        [k, v] for k, v in sorted(times.items(), key=lambda kv: -kv[1])[:n]
    ]


def idle_gaps(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """(start, end) of every stretch between the first and the last
    operation in which nothing ran."""
    merged = merge_intervals((e.start, e.end) for e in events)
    return [(a[1], b[0]) for a, b in zip(merged, merged[1:])]


def covering_span(spans: Sequence[Event], at: float) -> str:
    """The innermost host span that holds the instant `at`."""
    best: Optional[Event] = None
    for s in spans:
        if s.start <= at < s.end and (best is None or s.dur < best.dur):
            best = s
    return best.name if best is not None else NO_SPAN


def attribute_gaps(
    gaps: Sequence[Tuple[float, float]], spans: Sequence[Event]
) -> Dict[str, float]:
    """Idle seconds by what the host was doing at the middle of each
    gap (the program's span names; `NO_SPAN` where none was open)."""
    out: Dict[str, float] = {}
    for a, b in gaps:
        name = covering_span(spans, (a + b) / 2)
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def is_collective(e: Event) -> bool:
    """By opcode where the event carries one: JAX names the instruction
    after its own primitive (`%psum.5 = f32[...] all-reduce(...)`)."""
    return bool(COLLECTIVE.match(e.opcode or e.name))


def collective_seconds(ops: Sequence[Event],
                       async_ops: Sequence[Event] = ()) -> Tuple[float, float]:
    """(total, exposed) seconds of collectives on one device.

    The operations line runs one thing at a time, so the time its
    collective operations take on it — a synchronous collective, or the
    wait in an asynchronous one's `-done` — is time no compute ran:
    exposed. The total also counts what ran behind compute: the
    asynchronous line holds each such operation from start to done."""
    on_line = [e for e in ops if is_collective(e)]
    exposed = sum(e.dur for e in on_line)
    spans = [(e.start, e.end) for e in on_line] + [
        (e.start, e.end) for e in async_ops if is_collective(e)
    ]
    return sum(b - a for a, b in merge_intervals(spans)), exposed


def reduce_device(events: Sequence[Event],
                  async_ops: Sequence[Event] = ()) -> dict:
    """One device's operations -> busy, window, times by label, gaps."""
    if not events:
        return {"busy_s": 0.0, "window_s": 0.0, "self": {}, "gaps": [],
                "coll_total_s": 0.0, "coll_exposed_s": 0.0, "kernel_s": 0.0}
    first = min(e.start for e in events)
    last = max(e.end for e in events)
    coll_total, coll_exposed = collective_seconds(events, async_ops)
    own = self_seconds(events)
    by_label: Dict[str, float] = {}
    for e, s in own:
        by_label[label(e)] = by_label.get(label(e), 0.0) + s
    return {
        "busy_s": busy_seconds(events),
        "window_s": last - first,
        "self": by_label,
        "gaps": idle_gaps(events),
        "coll_total_s": coll_total,
        "coll_exposed_s": coll_exposed,
        "kernel_s": sum(s for e, s in own if e.opcode == KERNEL_OPCODE),
    }


def reduce_trace(devices: Dict[str, Sequence[Event]],
                 host_spans: Sequence[Event] = (),
                 async_ops: Optional[Dict[str, Sequence[Event]]] = None,
                 ) -> dict:
    """All devices' operations (+ the program's host spans on the same
    clock) -> what the last line and the per-layer readers take.

    `busy_s` is averaged over the devices and `window_s` runs from the
    first operation on any device to the last; times by label, gaps and
    collectives are those of the first device (the ranks of one SPMD
    program run the same schedule)."""
    async_ops = async_ops or {}
    per = {
        name: reduce_device(evs, async_ops.get(name, ()))
        for name, evs in devices.items()
    }
    live = {k: v for k, v in per.items() if v["busy_s"] > 0}
    if not live:
        raise RuntimeError(
            "the trace holds no device operation: nothing ran on the "
            "device in the traced window"
        )
    starts = [min(e.start for e in devices[k]) for k in live]
    ends = [max(e.end for e in devices[k]) for k in live]
    first = live[sorted(live)[0]]
    return {
        "devices": len(live),
        "busy_s": sum(v["busy_s"] for v in live.values()) / len(live),
        "window_s": max(ends) - min(starts),
        "device_ops": top(first["self"]),
        "idle_gaps": top(attribute_gaps(first["gaps"], host_spans)),
        "longest_gap_s": max(
            (b - a for a, b in first["gaps"]), default=0.0
        ),
        "device0_busy_s": first["busy_s"],
        "device0_window_s": first["window_s"],
        "coll_total_s": first["coll_total_s"],
        "coll_exposed_s": first["coll_exposed_s"],
        "kernel_s": first["kernel_s"],
    }


# ------------------------------------------------------------ the file


def find_xplane(trace_dir: str) -> str:
    import glob
    import os

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"
    )))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@dataclasses.dataclass
class Xplane:
    devices: Dict[str, List[Event]]    # operations by device plane
    async_ops: Dict[str, List[Event]]  # asynchronous operations, likewise
    programs: Dict[str, List[float]]   # first device: seconds of each run
                                       # of each compiled program
    offset: Optional[float]            # perf_counter - profiler clock, s


def read_xplane(path: str) -> Xplane:
    """What the reduction needs of an `.xplane.pb`. `offset` comes from
    the benchmark's sync annotation; None when the trace holds none."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    async_ops: Dict[str, List[Event]] = {}
    programs: Dict[str, List[float]] = {}
    cpu_ops: List[Event] = []
    offset = None
    for plane in data.planes:
        is_device = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if is_device and line.name in (OPS_LINE, ASYNC_LINE):
                into = devices if line.name == OPS_LINE else async_ops
                into[plane.name] = [
                    device_event(
                        e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9
                    )
                    for e in line.events if e.duration_ns > 0
                ]
                continue
            if is_device and line.name == MODULES_LINE:
                # The ranks of one SPMD program run the same programs:
                # the first device's runs stand for all.
                if int(is_device.group(1)) == 0:
                    for e in line.events:
                        # `jit_paged_decode_step(2234...)`: drop the hash
                        programs.setdefault(e.name.split("(")[0], []).append(
                            e.duration_ns * 1e-9
                        )
                continue
            if is_device:
                continue
            on_cpu = CPU_OPS_LINE.match(line.name)
            for e in line.events:
                if e.name.startswith(SYNC_PREFIX) and offset is None:
                    perf_ns = int(e.name[len(SYNC_PREFIX):])
                    offset = perf_ns * 1e-9 - e.start_ns * 1e-9
                elif on_cpu and e.duration_ns > 0:
                    cpu_ops.append(Event(
                        e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9
                    ))
    if not devices and cpu_ops:
        devices["/host:CPU (rehearsal)"] = cpu_ops
    return Xplane(devices, async_ops, programs, offset)
