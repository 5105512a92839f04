"""The FLOP and byte functions against hand counts at tiny shapes (the
model's step in ``models/sage.py``, the kernel's calls in
``kernels/segment_agg.py``), and the readers that divide them by trace
time."""
import pytest

from perfbench import byname
from perfbench.harness import MODELS, EpochOut
from perfbench.readers import Context, load_reader
from perfbench.trace import KERNELS, OTHER_KERNEL, Trace

DIMS = (2, 3, 4)                 # D, H, C
sage = byname.load("model", "sage", MODELS)
agg = byname.load("kernel", "segment_agg", KERNELS)


def test_aggregation_counts():
    assert agg.agg_flops(10, 4) == 40                 # one add per element
    # 10 source rows of 4 float32 read, 2 int32 ids per edge, 3 rows written
    assert agg.agg_bytes(10, 3, 4) == 10 * 16 + 10 * 8 + 3 * 16


def test_sampled_step_counts():
    # target: 2 matmuls (2x3) = 24, mean of 2 neighbours of width 2 = 4
    # hop: 2 neighbours x (24 + mean of 1 sample of width 2 = 2) = 52
    # layer 2: 2 matmuls (3x4) = 48, mean of 2 rows of width 3 = 6
    assert sage.sampled_seed_flops(DIMS, (2, 1)) == 28 + 52 + 54


def test_fullgraph_step_counts():
    # 5 owned rows, 7 edges: layer 1 = 4*5*2*3 + 7*2, layer 2 = 4*5*3*4 + 7*3
    assert sage.fullgraph_step_flops(DIMS, 5, 7) == 134 + 261


def test_aggregation_calls_per_epoch():
    assert agg.eval_agg_calls(DIMS, 5, 7) == [(7, 5, 2), (7, 5, 3)]
    # forward of both layers, transpose of layer 2 only (layer 1's input is
    # the features), into owned and halo rows
    assert agg.fullgraph_agg_calls(DIMS, 5, 2, 7) == [
        (7, 5, 2), (7, 5, 3), (7, 7, 3)]


def ctx(kind, epochs, kernel_ms=1.0, placement=None):
    ms = 1_000_000
    tr = Trace(window=(0, 10 * ms),
               devices={0: [("m/%closed_call.2 custom-call f32[8,3]"
                             " [segment_agg]", 0, int(kernel_ms * ms)),
                            ("m/fusion", 5 * ms, 6 * ms)]})
    return Context(trace=tr, dev=0, chips=1 if placement is None else 2,
                   peaks={"bf16_flops": 1e9, "hbm_bytes_per_s": 1e6},
                   kind=kind, dims=DIMS, fanouts=(2, 1), epochs=epochs,
                   owned=[5, 6], halo=[2, 1], edges=[7, 8], model=sage,
                   placement=placement)


def epoch(nodes, steps=1):
    return EpochOut(losses=None, nodes=nodes, steps=steps)


def test_train_flops_and_mfu():
    c = ctx("sampled", [epoch(10), epoch(20)])
    assert c.train_flops() == 3 * 134 * 30
    assert load_reader("train_mfu")(c) == pytest.approx(
        100 * 3 * 134 * 30 / (0.01 * 1e9))
    f = ctx("fullgraph", [epoch(11, steps=2)])
    per_step = sage.fullgraph_step_flops(DIMS, 5, 7) + \
        sage.fullgraph_step_flops(DIMS, 6, 8)
    assert f.train_flops() == 3 * per_step * 2


def test_roofline_and_time_share():
    c = ctx("fullgraph", [epoch(11)], kernel_ms=2.0)
    calls = agg.calls(c)
    assert len(calls) == 2 * (2 + 3)           # two partitions on one chip
    moved = sum(agg.agg_bytes(e, r, d) for e, r, d in calls)
    assert load_reader("segment_agg.roofline")(c) == pytest.approx(
        100 * (moved / 1e6) / 0.002)
    assert load_reader("segment_agg.time_share")(c) == pytest.approx(20.0)
    assert load_reader("device.idle_share")(c) == pytest.approx(70.0)


@pytest.mark.parametrize("name", ["segment_agg.roofline",
                                  "segment_agg.time_share"])
def test_kernel_readers_refuse_another_kernel(name):
    c = ctx("fullgraph", [epoch(11)])
    c.trace.devices[0].append(
        ("m/%closed_call.9 custom-call f32[8,3]" + OTHER_KERNEL, 7, 8))
    with pytest.raises(ValueError, match="no file of perfbench/kernels"):
        load_reader(name)(c)


def test_kernel_calls_of_one_chip_of_several():
    """With one partition to a chip, the fullest chip's kernel time is
    held against its own partition's calls alone, and the model FLOPs
    against every chip's peak."""
    c = ctx("fullgraph", [epoch(11)], kernel_ms=2.0,
            placement={0: [1], 1: [0]})
    calls = agg.calls(c)
    assert calls == (agg.eval_agg_calls(DIMS, 6, 8)
                     + agg.fullgraph_agg_calls(DIMS, 6, 1, 8))
    moved = sum(agg.agg_bytes(e, r, d) for e, r, d in calls)
    assert load_reader("segment_agg.roofline")(c) == pytest.approx(
        100 * (moved / 1e6) / 0.002)
    per_step = sage.fullgraph_step_flops(DIMS, 5, 7) + \
        sage.fullgraph_step_flops(DIMS, 6, 8)
    assert load_reader("train_mfu")(c) == pytest.approx(
        100 * 3 * per_step / (0.01 * 2 * 1e9))
