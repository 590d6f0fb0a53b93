"""The trace reduction on a synthetic trace (busy union, self time, top
operations, gap attribution, collectives) and on a small trace recorded
here by the same helpers the drivers use."""

import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import spans, trace_reduce as tr  # noqa: E402
from benchmark.harness.trace_reduce import Event  # noqa: E402


def test_merge_intervals_unions_overlaps_and_drops_empties():
    got = tr.merge_intervals([(5, 6), (0, 2), (1, 3), (3, 3), (2.5, 2.7), (6, 7)])
    assert got == [(0, 3), (5, 7)]


def test_busy_is_the_union_not_the_sum():
    events = [Event("a", 0.0, 2.0), Event("b", 1.0, 2.0), Event("c", 5.0, 1.0)]
    assert tr.busy_seconds(events) == pytest.approx(4.0)


def test_self_time_takes_nested_operations_out_of_their_parent():
    events = [
        Event("while.1", 0.0, 10.0),
        Event("fusion.1", 1.0, 2.0), Event("fusion.2", 4.0, 3.0),
        Event("copy.3", 4.5, 1.0),          # inside fusion.2
        Event("fusion.1", 12.0, 1.0),       # outside the loop, same name
    ]
    got = tr.self_times(events)
    assert got == pytest.approx(
        {"while.1": 5.0, "fusion.1": 3.0, "fusion.2": 2.0, "copy.3": 1.0})
    assert sum(got.values()) == pytest.approx(tr.busy_seconds(events))


@pytest.mark.parametrize("name,group", [
    ("fusion.123", "fusion"), ("all-gather-start.4", "all-gather-start"),
    ("convolution", "convolution"), ("copy.1.2", "copy"), ("7", "7"),
])
def test_op_group_strips_the_instance_number(name, group):
    assert tr.op_group(name) == group


def test_device_events_keep_the_instruction_name_and_result_shape():
    """On the v5e an operation's event name is its whole HLO instruction."""
    e = tr.device_event(
        "%copy.315 = bf16[12,2048,16,12,64]{4,2,3,1,0:T(8,128)(2,1)} "
        "copy(bf16[12,2048,16,12,64]{1,4,3,2,0:T(8,128)(2,1)} %cache__v__.1)",
        1.0, 0.5)
    assert (e.name, e.shape) == ("copy.315", "bf16[12,2048,16,12,64]")
    assert e.opcode == "copy"
    assert tr.label(e) == "copy bf16[12,2048,16,12,64]"
    t = tr.device_event(
        "%sort = (f32[16384,50257]{0,1:T(8,128)}, s32[16384,50257]{0,1}) "
        "sort(f32[16384,50257]{0,1:T(8,128)} %copy.941, ...)", 0.0, 1.0)
    assert tr.label(t) == "sort f32[16384,50257]" and t.opcode == "sort"
    k = tr.device_event(
        "%jvp__.22 = (bf16[16,12,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}, "
        "f32[16,12,1024,128]{3,2,1,0:T(8,128)}) custom-call(bf16[16,12,1024,64]"
        "{3,2,1,0:T(8,128)(2,1)} %bitcast.1257, ...)", 0.0, 1.0)
    assert k.opcode == tr.KERNEL_OPCODE and k.name == "jvp__.22"
    f = tr.device_event(
        "%fusion.695 = (f32[768,50257]{0,1:T(8,128)}, f32[768,50257]{0,1}) "
        "fusion(f32[768,50257]{0,1:T(8,128)} %p, f32[]{:T(128)S(6)} %c), "
        "kind=kOutput, calls=%fused_computation", 0.0, 1.0)
    assert f.opcode == "fusion"
    g = tr.device_event(
        "%all-gather-start.3 = (f32[8]{0}, f32[32]{0}) all-gather-start(...)",
        0.0, 1.0)
    assert tr.COLLECTIVE.match(g.name)
    plain = tr.device_event("dot_general.1", 0.0, 1.0)
    assert (plain.name, plain.shape) == ("dot_general.1", "")


def test_top_ranks_and_self_times_group_by_label():
    events = [Event("fusion.1", 0.0, 1.0, "f32[8]"), Event("fusion.2", 1.0, 2.5, "f32[8]"),
              Event("copy.1", 4.0, 3.0, "bf16[4,4]"), Event("x", 8.0, 0.1)]
    got = tr.self_times(events, tr.label)
    assert tr.top(got, 2) == [["fusion f32[8]", 3.5], ["copy bf16[4,4]", 3.0]]


def test_idle_gaps_lie_between_first_and_last_operation():
    events = [Event("a", 1.0, 1.0), Event("b", 3.0, 1.0), Event("c", 3.5, 2.0),
              Event("d", 9.0, 1.0)]
    assert tr.idle_gaps(events) == [(2.0, 3.0), (5.5, 9.0)]


def test_gaps_are_attributed_to_the_innermost_covering_host_span():
    host = [Event("step", 0.0, 10.0), Event("decode_step", 2.0, 1.5),
            Event("fetch", 20.0, 1.0)]
    gaps = [(2.1, 2.9), (5.0, 6.0), (6.5, 7.0), (12.0, 13.0)]
    got = tr.attribute_gaps(gaps, host)
    assert got == pytest.approx(
        {"decode_step": 0.8, "step": 1.5, tr.NO_SPAN: 1.0})


def test_collectives_total_counts_start_to_done_exposed_counts_the_line():
    ops = [
        Event("all-gather-start.1", 0.0, 0.1),
        Event("fusion.1", 0.1, 2.0),              # compute hides the gather
        Event("all-gather-done.1", 2.1, 0.4),     # the wait that is left
        Event("all-reduce.7", 3.0, 1.0),          # synchronous: all exposed
        Event("fusion.2", 4.0, 1.0),
        Event("copy-start.9", 5.0, 0.1),          # not a collective
    ]
    async_ops = [Event("all-gather-start.1", 0.0, 2.5),
                 Event("copy-start.9", 5.0, 3.0)]
    total, exposed = tr.collective_seconds(ops, async_ops)
    assert exposed == pytest.approx(0.1 + 0.4 + 1.0)
    assert total == pytest.approx(2.5 + 1.0)
    # the opcode decides where there is one: JAX names an all-reduce `psum`
    named = [Event("psum.5", 0.0, 2.0, "f32[48,6400,1600]", "all-reduce"),
             Event("all-gather_fusion.1", 2.0, 1.0, "", "fusion")]
    assert tr.collective_seconds(named) == pytest.approx((2.0, 2.0))
    # without the asynchronous line only what the line shows is counted
    assert tr.collective_seconds(ops) == pytest.approx((1.5, 1.5))


def test_kernel_time_is_the_custom_calls_self_time():
    events = [Event("fusion.1", 0.0, 1.0, "", "fusion"),
              Event("jvp__.3", 1.0, 0.5, "", "custom-call"),
              Event("transpose_jvp___.4", 2.0, 0.25, "", "custom-call")]
    assert tr.reduce_device(events)["kernel_s"] == pytest.approx(0.75)


def test_reduce_trace_averages_busy_over_devices_and_spans_the_window():
    dev0 = [Event("fusion.1", 10.0, 1.0), Event("fusion.2", 12.0, 2.0)]
    dev1 = [Event("fusion.1", 10.5, 1.0), Event("fusion.2", 12.0, 1.0)]
    host = [Event("sync", 11.0, 1.0)]
    got = tr.reduce_trace(
        {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, host)
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx(2.5)
    assert got["window_s"] == pytest.approx(4.0)
    assert got["device_ops"] == [["fusion", 3.0]]
    assert got["coll_total_s"] == got["coll_exposed_s"] == 0
    assert got["idle_gaps"] == [["sync", 1.0]]
    assert got["longest_gap_s"] == pytest.approx(1.0)


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(RuntimeError, match="no device operation"):
        tr.reduce_trace({"/device:TPU:0": []})
    with pytest.raises(RuntimeError, match="no device operation"):
        tr.reduce_trace({})


class _Fake:
    def __init__(self, **kw):
        self.__dict__.update(kw)


def _ev(name, start_ms, dur_ms):
    return _Fake(name=name, start_ns=int(start_ms * 1e6),
                 duration_ns=int(dur_ms * 1e6))


def test_program_runs_are_read_from_the_first_devices_modules_line(monkeypatch):
    """The shape of a v5e trace (names as the chip wrote them, PR 22):
    each run of a compiled program is one event on `XLA Modules`, under
    the program's jitted name and a hash; the reduction keeps the first
    device's runs, and a run cut short by the slice's edge does not move
    the median."""
    import jax.profiler

    step = "jit_paged_decode_step(2234286149522121710)"
    op = ("%copy.315 = bf16[12,2048,16,12,64]{1,4,3,2,0:T(8,128)(2,1)} "
          "copy(bf16[12,2048,16,12,64]{4,3,2,1,0} %p)")

    def plane(n, runs):
        return _Fake(name=f"/device:TPU:{n}", lines=[
            _Fake(name="XLA Modules", events=[
                _ev(step, 75.0 * i, d) for i, d in enumerate(runs)
            ] + [_ev("jit_copy_page(7872090032300639488)", 400.0, 1.3)]),
            _Fake(name="XLA Ops", events=[_ev(op, 0.0, 15.0)]),
        ])

    host = _Fake(name="/host:CPU", lines=[_Fake(name="python", events=[
        _ev(f"{tr.SYNC_PREFIX}{5_000_000_000}", 1000.0, 0.001)])])
    data = _Fake(planes=[
        plane(0, [12.0, 70.33, 70.32, 70.35, 70.34]),  # first run cut
        plane(1, [99.0]), host,
    ])
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))
    monkeypatch.setattr(spans, "find_xplane", lambda path: path)

    x = tr.read_xplane("unused")
    assert x.programs["jit_paged_decode_step"] == pytest.approx(
        [0.012, 0.07033, 0.07032, 0.07035, 0.07034])
    assert x.offset == pytest.approx(5.0 - 1.0)
    got = spans.reduce_dir("unused", [])
    assert set(got["program_seconds"]) == {"jit_paged_decode_step",
                                           "jit_copy_page"}
    assert got["program_median_s"]["jit_paged_decode_step"] == pytest.approx(
        0.07033)
    assert got["program_seconds"]["jit_paged_decode_step"] == pytest.approx(
        0.29334)
    assert got["device_ops"] == [
        ["copy bf16[12,2048,16,12,64]", pytest.approx(0.015)]]


def test_a_recorded_trace_reduces_and_its_clock_is_synced():
    """Recorded here on the CPU by the drivers' own helpers: the sync
    annotation is found, operations are found, and a host span that
    covers the work ends up on the profiler's clock."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with spans.trace_dir() as tdir:
        with spans.profiled(tdir):
            t0 = time.perf_counter()
            for _ in range(5):
                f(x).block_until_ready()
            t1 = time.perf_counter()
        x = tr.read_xplane(tr.find_xplane(tdir))
        got = spans.reduce_dir(tdir, [Event("work", t0, t1 - t0)])
    assert x.offset is not None and got["clock_synced"]
    assert x.devices and all(x.devices.values())
    assert got["busy_s"] > 0 and got["window_s"] >= got["busy_s"]
    assert got["window_s"] <= (t1 - t0) * 1.5 + 0.05
    assert 1 <= len(got["device_ops"]) <= 10
    # every gap between the five calls lies inside the "work" span
    assert all(name == "work" for name, _ in got["idle_gaps"])
    assert not os.path.exists(tdir)
