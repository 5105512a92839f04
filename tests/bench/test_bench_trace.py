"""The reduction from a trace to per-layer numbers, on a hand-built trace."""
import pytest

from perfbench.trace import Trace, union_ns

MS = 1_000_000

# HLO texts of ops as a v5e trace names them (layouts kept, operands cut)
KERNEL = ('%closed_call.21 = f32[94208,256]{1,0:T(8,128)S(1)} custom-call('
          's32[12919]{0:T(1024)S(1)} %dynamic-slice_reduce_fusion.7, '
          's32[12919,1,128]{2,1,0:T(1,128)S(1)} %dynamic-slice_bitcast_fusion.20, '
          'f32[12919,1,128]{2,1,0:T(1,128)S(1)} %dynamic-slice_bitcast_fusion.21, '
          'f32[1653632,256]{1,0:T(8,128)} %dynamic-slice_bitcast_fusion.22, '
          'f32[94208,1]{1,0:T(8,128)} %copy.97), '
          'custom_call_target="tpu_custom_call", '
          'frontend_attributes={kernel_metadata={}}')
FUSED_KERNEL = ('%closed_call.37 = f32[4,60928,256]{2,1,0:T(8,128)} fusion('
                'f32[4,60928,256]{2,1,0:T(8,128)} %get-tuple-element.826, '
                's32[]{:T(128)} %get-tuple-element.825, '
                's32[2179]{0:T(1024)S(1)} %dynamic-slice_reduce_fusion.11, '
                's32[2179,1,128]{2,1,0:T(1,128)S(1)} %dynamic-slice_bitcast_fusion.41, '
                'f32[2179,1,128]{2,1,0:T(1,128)S(1)} %dynamic-slice_bitcast_fusion.42, '
                'f32[278912,256]{1,0:T(8,128)} %dynamic-slice_bitcast_fusion.43), '
                'kind=kCustom, calls=%fused_computation.29.clone')
GATHER = ('%fusion.3 = f32[6614528,256]{1,0:T(8,128)} fusion('
          'f32[4,94086,256]{2,0,1:T(4,128)} %bitcast.14, '
          's32[6614528]{0:T(1024)S(1)} %custom-call.18), kind=kCustom, '
          'calls=%fused_computation.3')
OTHER = ('%closed_call.5 = f32[4096,128]{1,0} custom-call('
         'f32[4096,128]{1,0} %p0, s32[4096]{0} %p1), '
         'custom_call_target="tpu_custom_call"')


def trace():
    # window 0..100 ms; device 0 busy 10..30 (two overlapping ops) and
    # 50..60; device 1 busy 0..90
    return Trace(
        window=(0, 100 * MS),
        devices={
            0: [("m/fusion.1", 10 * MS, 25 * MS),
                ("m/segment_agg", 20 * MS, 30 * MS),
                ("m/all-to-all.3", 50 * MS, 60 * MS),
                ("m/fusion.1", 110 * MS, 120 * MS)],     # after the window
            1: [("m/fusion.2", -5 * MS, 90 * MS)],       # clipped at 0
        },
        host=[("bench.window", 0, 100 * MS),
              ("bench.draw_wait", 60 * MS, 100 * MS),
              ("bench.epoch", 0, 100 * MS),
              ("PjitFunction", 30 * MS, 36 * MS)])


@pytest.mark.parametrize("intervals,expect", [
    ([], 0), ([(0, 5)], 5), ([(0, 5), (3, 9)], 9), ([(0, 5), (6, 9)], 8),
    ([(3, 9), (0, 5), (4, 6)], 9)])
def test_union(intervals, expect):
    assert union_ns(intervals) == expect


def test_busy_idle_and_fullest_device():
    t = trace()
    assert t.busy_ns(0) == 30 * MS
    assert t.busy_ns(1) == 90 * MS
    assert t.fullest_device() == 1
    assert t.window_ns == 100 * MS


def test_kernel_and_all_to_all_time():
    t = trace()
    assert t.op_ns(0, lambda n: "segment_agg" in n) == 10 * MS
    assert t.op_ns(0, lambda n: "all-to-all" in n) == 10 * MS
    assert t.op_ns(0, lambda n: "fusion" in n) == 15 * MS


def test_idle_gaps_named_by_host_activity():
    gaps = trace().idle_gaps(0)
    # gaps on device 0: 60..100 (40 ms), 0..10 (10 ms), 30..50 (20 ms)
    assert [round(s * 1e3) for _, s in gaps] == [40, 20, 10]
    assert gaps[0][0] == "bench.draw_wait"
    # 30..50: PjitFunction covers 6 of 20 ms; the epoch span covers it all
    assert gaps[1][0] == "bench.epoch"


def test_top_ops():
    ops = trace().top_ops(0)
    assert ops[0][0] == "m/fusion.1" and ops[0][1] == pytest.approx(0.015)
    assert {n for n, _ in ops} == {"m/fusion.1", "m/segment_agg",
                                   "m/all-to-all.3"}


@pytest.mark.parametrize("text,short", [
    ("%fusion.3 = f32[6614528,256]{1,0:T(8,128)} fusion(f32[4,94086,256]"
     "{2,0,1:T(4,128)} %bitcast.14), kind=kCustom, calls=%fused_computation.3",
     "%fusion.3 fusion f32[6614528,256]"),
    (KERNEL, "%closed_call.21 custom-call f32[94208,256] [segment_agg]"),
    ("%while.7 = (s32[]{:T(128)}, bf16[4,94208,100]{1,2,0:T(8,128)(2,1)}) "
     "while((s32[]{:T(128)}) %tuple.85)", "%while.7 while (s32[], bf16[4,94208,100])"),
    ("ThreadpoolListener::Record", "ThreadpoolListener::Record")])
def test_short_op_names(text, short):
    from perfbench.trace import short_name

    assert short_name(text) == short


@pytest.mark.parametrize("text,tag", [
    (KERNEL, " [segment_agg]"), (FUSED_KERNEL, " [segment_agg]"),
    (GATHER, ""), (OTHER, " [pallas]")])
def test_kernel_ops_recognised(text, tag):
    from perfbench import byname
    from perfbench.trace import KERNELS, short_name

    is_segment_agg = byname.load("kernel", "segment_agg",
                                 KERNELS).is_segment_agg

    name = short_name(text)
    tags = (" [segment_agg]", " [pallas]")
    assert name.endswith(tag) if tag else not name.endswith(tags)
    assert is_segment_agg("with_resident#029719/" + name) is (
        tag == " [segment_agg]")


def test_halo_a2a_share_reads_the_fullest_chips_all_to_all():
    from perfbench.readers import Context, load_reader

    def ctx(t, dev):
        return Context(trace=t, dev=dev, chips=2, peaks={}, kind="fullgraph",
                       dims=(1, 1), fanouts=(), epochs=[], owned=[1, 1],
                       halo=[0, 0], edges=[0, 0])

    read = load_reader("halo.a2a_share")
    t = trace()
    # device 0: the all-to-all runs 50..60 ms of the 100 ms window
    assert read(ctx(t, 0)) == pytest.approx(10.0)
    # as the trace prints an op: program, instruction, opcode, shape; an
    # async pair counts both halves, and an op after the window nothing
    t.devices[0] += [
        ("p#1/%all-to-all-start.2 all-to-all-start (f32[4,9,256])",
         62 * MS, 64 * MS),
        ("p#1/%all-to-all-done.2 all-to-all-done f32[4,9,256]",
         64 * MS, 65 * MS),
        ("p#1/%fusion.7 fusion f32[4,9,256]", 66 * MS, 70 * MS),
        ("p#1/%all-to-all.4 all-to-all f32[4,9,256]", 120 * MS, 130 * MS)]
    assert read(ctx(t, 0)) == pytest.approx(13.0)
    # device 1 ran no exchange (as one chip's vmapped copy): nothing read
    assert read(ctx(t, 1)) is None
