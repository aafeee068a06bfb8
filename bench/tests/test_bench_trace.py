"""The reduction from a profiler trace to device busy time, idle share,
per-step busy time and collective time, on a small recorded trace."""

from pathlib import Path

import pytest

from bench import hlo_groups, trace

PROBE = Path(__file__).parent / "data" / "v5e_probe.xplane.pb"


def ev(name, start, dur):
    return trace.Event(name, float(start), float(dur))


def test_union_merges_overlaps_and_keeps_order():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]


def test_busy_and_gaps_clip_to_the_window():
    ops = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 10), ev("d", 95, 20)]
    assert trace.busy_ns(ops, 0, 100) == 15 + 10 + 5
    assert trace.idle_gaps(ops, 0, 100) == [(15, 30), (40, 95)]
    assert trace.busy_ns(ops, 8, 32) == 7 + 2


def test_op_self_time_leaves_out_nested_ops():
    ops = [ev("while.1", 0, 100), ev("fusion.2", 10, 30), ev("fusion.2", 50, 30),
           ev("fusion.3", 120, 10)]
    assert trace.op_totals(ops, 0, 1000) == {
        "while.1": pytest.approx(40e-9), "fusion.2": pytest.approx(60e-9),
        "fusion.3": pytest.approx(10e-9)}
    assert trace.busy_ns(ops, 0, 1000) == 110


def test_summary_reads_steps_spans_and_devices():
    spans = [ev("bench.data", 0, 40), ev("bench.step", 40, 5), ev("bench.readback", 45, 55),
             ev("bench.data", 100, 40), ev("bench.step", 140, 5),
             ev("bench.readback", 145, 55)]
    dev0 = [ev("fusion.1", 42, 50), ev("all-reduce-start.3", 92, 4),
            ev("all-reduce-done.3", 96, 2), ev("fusion.1", 142, 50)]
    dev1 = [ev("fusion.1", 44, 60), ev("fusion.1", 144, 60)]
    tr = trace.Trace({"/device:TPU:0": dev0, "/device:TPU:1": dev1}, spans)
    lo, hi = trace.window_bounds(tr)
    s = trace.summarize(tr, lo, hi, steps=2)
    assert (lo, hi) == (0, 200) and s.window_s == pytest.approx(200e-9)
    assert s.busy_s == pytest.approx((106 + 116) / 2 * 1e-9)   # device 1 clipped at 200
    assert s.span_count == {"bench.data": 2, "bench.step": 2, "bench.readback": 2}
    assert s.span_s["bench.data"] == pytest.approx(80e-9)
    # the longest gap is the data wait of the second step, on device 0
    assert s.gaps[0] == ("bench.data", pytest.approx(44e-9))
    # 6 ns of all-reduce on one of two devices, over two steps
    assert trace.collective_ms_per_step(s, ["all-reduce.3"]) == pytest.approx(1.5e-6)
    assert trace.collective_ms_per_step(s, ["all-reduce-start.3"]) == pytest.approx(1.5e-6)
    assert trace.collective_ms_per_step(s, ["all-gather.9"]) is None
    b = trace.breakdown(s, top=2)
    assert b["device_ops"][0][0] == "fusion.1" and len(b["idle_gaps"]) == 2


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5e: three host spans per step around a
    jitted bf16 matmul chain, read with nothing but JAX."""
    tr = trace.load_file(PROBE)
    assert list(tr.device_ops) == ["/device:TPU:0"]
    assert [s.name for s in tr.spans] == ["bench.data", "bench.step", "bench.readback"] * 2
    names = {e.name for e in tr.device_ops["/device:TPU:0"]}
    assert {"convolution_tanh_fusion", "fusion", "dynamic_slice.1"} <= names
    lo, hi = trace.window_bounds(tr)
    s = trace.summarize(tr, lo, hi, steps=2)
    # two 8192^3 bf16 matmul pairs of ~11.8 ms each
    assert s.busy_s == pytest.approx(2 * 11.8e-3, rel=0.02)
    assert 0 < s.busy_s < s.window_s
    assert s.gaps[0][0] == "bench.readback"
    assert s.span_count["bench.data"] == 2


HLO = """
  %all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %p), channel_id=1, replica_groups={{0,2},{1,3}}, to_apply=%add
  %all-reduce-start.2 = f32[8]{0} all-reduce-start(f32[8]{0} %q), channel_id=2, replica_groups=[2,2]<=[4], to_apply=%add
  %all-gather.4 = f32[16]{0} all-gather(f32[8]{0} %r), channel_id=4, replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}
  %fusion.5 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%f
"""


def test_pod_collectives_are_named_from_replica_groups():
    mesh = {"pod": 2, "data": 2, "model": 1}
    assert hlo_groups.classify_groups("replica_groups={{0,2},{1,3}}", mesh) == (
        frozenset({"pod"}), 2)
    assert hlo_groups.classify_groups("replica_groups=[2,2]<=[4]", mesh) == (
        frozenset({"data"}), 2)
    assert hlo_groups.collectives_over(HLO, mesh, "pod") == {
        "all-reduce.1": "all-reduce", "all-gather.4": "all-gather"}
