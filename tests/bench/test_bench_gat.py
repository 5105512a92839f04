"""The GAT model module ``models/gat.py`` and its kernel files
``kernels/gat_agg.py`` and ``kernels/gat_sddmm.py``, on the CPU at a tiny
size: the module's plain forward is the program's, the counts are the
hand counts, and each kernel file claims its own ops and no other's."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import byname, graphgen, harness
from perfbench.harness import EpochOut
from perfbench.readers import Context, load_reader
from perfbench.reference import Edges, edge_blocks
from perfbench.trace import KERNELS, OTHER_KERNEL, Trace, short_name

from conftest import DATA

gat = byname.load("model", "gat", harness.MODELS)
gat_agg = byname.load("kernel", "gat_agg", KERNELS)
gat_sddmm = byname.load("kernel", "gat_sddmm", KERNELS)
segment_agg = byname.load("kernel", "segment_agg", KERNELS)

# float32 at `highest` on the CPU: the two forwards differ in the order of
# their sums alone (per destination a few hundred terms, a softmax's exp),
# a few float32 ulps (2**-23) of the largest value
RTOL = 2e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = harness.load_json(DATA / "tiny-gat.json")
    g = graphgen.generate(cfg)
    n = g.num_nodes
    dst = np.repeat(np.arange(n), np.diff(g.indptr)).astype(np.int32)
    src = g.indices.astype(np.int32)
    layers = gat.init_params(cfg, harness.seed_key(2**35 + 11))
    return cfg, g, src, dst, layers


def _edges(src, dst, n, block):
    s, d = edge_blocks(src, dst, n, block=block)
    inv = jnp.ones((n,), jnp.float32)   # GAT reads no degree
    return Edges(jnp.asarray(s), jnp.asarray(d), n, inv)


def test_reference_equals_the_programs_forward(tiny):
    """The plain forward, in edge blocks smaller than the graph (the
    softmax's two passes span blocks), equals the program's GAT through
    its Pallas kernels, in its logits and its loss gradient."""
    cfg, g, src, dst, layers = tiny
    model, loss_fn = gat.build_program(cfg)
    assert loss_fn is None
    n = g.num_nodes
    feats = jnp.asarray(g.features)
    edges = _edges(src, dst, n, block=1000)
    assert edges.src.shape[0] > 2
    params = gat.to_program_params(layers)

    def prog(p):
        return model.apply_full(p, feats, src, dst, n)

    def ref(ls):
        return gat.full_logits(ls, feats, edges)

    with jax.default_matmul_precision("highest"):
        want, got = jax.jit(ref)(layers), jax.jit(prog)(params)
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got, want, rtol=0, atol=RTOL * scale)
        g_ref = jax.jit(jax.grad(lambda ls: (ref(ls) ** 2).sum()))(layers)
        g_prog = jax.jit(jax.grad(lambda p: (prog(p) ** 2).sum()))(params)
    for a, b in zip(gat.from_program_params(g_prog), g_ref):
        for k in ("w", "a_src", "a_dst", "b"):
            np.testing.assert_allclose(
                a[k], b[k], rtol=0, atol=RTOL * float(np.abs(b[k]).max()))


def test_rows_logits_are_the_full_logits_of_those_rows(tiny):
    cfg, g, src, dst, layers = tiny
    n = g.num_nodes
    feats = jnp.asarray(g.features)
    rows = np.sort(np.asarray(g.val_idx, np.int64))
    deg = np.diff(g.indptr)[rows]
    last_src = np.concatenate([g.indices[g.indptr[r]:g.indptr[r + 1]]
                               for r in rows]).astype(np.int32)
    last_pos = np.repeat(np.arange(len(rows)), deg).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        full = gat.full_logits(layers, feats, _edges(src, dst, n, 1000))
        part = gat.rows_logits(layers, feats, _edges(src, dst, n, 1000),
                               jnp.asarray(rows, jnp.int32),
                               _edges(last_src, last_pos, len(rows), 700))
    np.testing.assert_allclose(part, np.asarray(full)[rows], rtol=0,
                               atol=RTOL * float(np.abs(full).max()))


def test_configuration_is_checked_against_the_published_heads(tiny):
    cfg = dict(tiny[0])
    assert gat.layer_dims(cfg) == (16, 32, 32, 30)
    with pytest.raises(ValueError, match="published heads"):
        gat.layer_dims({**cfg, "heads": [8, 8, 1]})
    with pytest.raises(ValueError, match="full-graph path"):
        gat.sampled_logits(None, None, None, None)


# -- counts ------------------------------------------------------------------
DIMS = (2, 8, 8, 12)             # D, K1*F, K2*F, K3*C (F = 2, C = 2)


def test_aggregation_and_sddmm_counts():
    # a multiply and an add per edge and element
    assert gat_agg.agg_flops(10, 8) == 160
    assert gat_sddmm.sddmm_flops(10, 8) == 160
    # 10 message rows of 8 float32, 2 int32 ids and 4 weights per edge,
    # 3 rows written
    assert gat_agg.agg_bytes(10, 3, 8, 4) == 10 * 32 + 10 * 8 + 10 * 16 + 3 * 32
    # 10 message rows, 3 cotangent rows read once, 2 ids per edge in, 4
    # weight gradients per edge out
    assert gat_sddmm.sddmm_bytes(10, 3, 8, 4) == (10 * 32 + 3 * 32 + 10 * 8
                                                  + 10 * 16)


def test_calls_per_epoch():
    heads = (4, 4, 6)
    assert gat_agg.eval_calls(DIMS, heads, 5, 7) == [
        (7, 5, 8, 4), (7, 5, 8, 4), (7, 5, 12, 6)]
    # per layer the forward, its recomputation in the backward pass (every
    # layer but the last), and the transpose into owned and halo rows
    assert gat_agg.fullgraph_calls(DIMS, heads, 5, 2, 7) == [
        (7, 5, 8, 4), (7, 5, 8, 4), (7, 7, 8, 4),
        (7, 5, 8, 4), (7, 5, 8, 4), (7, 7, 8, 4),
        (7, 5, 12, 6), (7, 7, 12, 6)]
    assert gat_sddmm.step_calls(DIMS, heads, 5, 7) == [
        (7, 5, 8, 4), (7, 5, 8, 4), (7, 5, 12, 6)]


# gat-flickr's widths: D 500, hidden 4 x 256, last 6 x 7
PPI_DIMS = (500, 1024, 1024, 42)


def test_calls_at_the_published_widths_go_two_heads_a_call():
    heads = (4, 4, 6)
    assert gat_agg.head_groups(1024, 4) == [2, 2]
    assert gat_agg.head_groups(42, 6) == [6]
    # each hidden call is 512 wide and reads all four heads' weights
    hidden = lambda rows: [(7, rows, 512, 4)] * 2
    assert gat_agg.eval_calls(PPI_DIMS, heads, 5, 7) == (
        hidden(5) * 2 + [(7, 5, 42, 6)])
    # a step: 2 x (forward, recomputation, transpose) x 2 groups, then the
    # last layer's forward and transpose; 14 calls, as the compiled step
    # holds (tests/test_tpu_compile.py)
    step = gat_agg.fullgraph_calls(PPI_DIMS, heads, 5, 2, 7)
    assert step == (hidden(5) * 2 + hidden(7)) * 2 + [(7, 5, 42, 6),
                                                      (7, 7, 42, 6)]
    # bytes of one hidden call: 512 lanes a message row and an output row,
    # two ids and four weights per edge
    assert gat_agg.agg_bytes(*step[0]) == (7 * 512 * 4 + 7 * 8 + 7 * 16
                                           + 5 * 512 * 4)
    # the SDDMM: one call a group, each writing its two heads' gradients
    assert gat_sddmm.step_calls(PPI_DIMS, heads, 5, 7) == (
        [(7, 5, 512, 2)] * 4 + [(7, 5, 42, 6)])
    assert gat_sddmm.sddmm_bytes(7, 5, 512, 2) == (7 * 512 * 4 + 5 * 512 * 4
                                                   + 7 * 8 + 7 * 8)


def test_train_flops():
    # per layer: projection 2*5*d_in*w, two scores a head 4*5*w, the
    # weighted sum over 7 edges and 5 self terms 2*(7+5)*w
    want = sum(2 * 5 * a * b + 4 * 5 * b + 2 * 12 * b
               for a, b in zip(DIMS[:-1], DIMS[1:]))
    assert gat.fullgraph_step_flops(DIMS, 5, 7) == want


def _ctx(kind="fullgraph", steps=2, agg_ms=2.0, sddmm_ms=1.0):
    ms = 1_000_000
    tr = Trace(window=(0, 10 * ms), devices={0: [
        ("m/%closed_call.2 custom-call f32[256,8] [gat_agg]", 0,
         int(agg_ms * ms)),
        ("m/%closed_call.3 custom-call f32[2,4,128] [gat_sddmm]", 3 * ms,
         3 * ms + int(sddmm_ms * ms)),
        ("m/fusion", 5 * ms, 6 * ms)]})
    return Context(trace=tr, dev=0, chips=1,
                   peaks={"bf16_flops": 1e9, "hbm_bytes_per_s": 1e6},
                   kind=kind, dims=DIMS, fanouts=(),
                   epochs=[EpochOut(losses=None, nodes=11, steps=steps)],
                   owned=[5, 6], halo=[2, 1], edges=[7, 8], model=gat)


def test_readers():
    c = _ctx()
    calls = gat_agg.calls(c)
    # two partitions: the eval forward (3) and two steps' forward,
    # recomputation and transpose (2 * 8)
    assert len(calls) == 2 * (3 + 16)
    moved = sum(gat_agg.agg_bytes(*x) for x in calls)
    assert load_reader("gat_agg.roofline")(c) == pytest.approx(
        100 * (moved / 1e6) / 0.002)
    assert load_reader("gat_agg.time_share")(c) == pytest.approx(20.0)
    s_calls = gat_sddmm.calls(c)
    assert len(s_calls) == 2 * 2 * 3                 # a step: one a layer
    moved = sum(gat_sddmm.sddmm_bytes(*x) for x in s_calls)
    assert load_reader("gat_sddmm.roofline")(c) == pytest.approx(
        100 * (moved / 1e6) / 0.001)
    per_step = gat.fullgraph_step_flops(DIMS, 5, 7) + \
        gat.fullgraph_step_flops(DIMS, 6, 8)
    assert c.train_flops() == 3 * per_step * 2
    assert load_reader("train_mfu")(c) == pytest.approx(
        100 * 3 * per_step * 2 / (0.01 * 1e9))


@pytest.mark.parametrize("name", ["gat_agg.roofline", "gat_agg.time_share",
                                  "gat_sddmm.roofline"])
def test_readers_silent_without_their_kernel_and_refuse_an_unclaimed_one(
        name):
    c = _ctx(agg_ms=0, sddmm_ms=0)
    assert load_reader(name)(c) is None
    c = _ctx()
    c.trace.devices[0].append(
        ("m/%closed_call.9 custom-call f32[8,3]" + OTHER_KERNEL, 7, 8))
    with pytest.raises(ValueError, match="no file of perfbench/kernels"):
        load_reader(name)(c)


# -- which file claims which op ----------------------------------------------
# HLO texts as a v5e trace gives the ops (layouts kept): GAT's forward
# aggregation (four heads, a call of two), its transpose, the narrow last
# layer (six heads), the SDDMM, an SDDMM fused by XLA into a kCustom
# fusion, and the mean kernel's op
GAT_AGG = ('%closed_call.193 = f32[60928,512]{1,0:T(8,128)} custom-call('
           's32[2416]{0:T(1024)S(1)} %param_2.1989, '
           's32[2416,1,128]{2,1,0:T(1,128)S(1)} %param_3.1270, '
           'f32[2416,4,128]{2,1,0:T(4,128)} %param_4.762, '
           'f32[309248,512]{1,0:T(8,128)} %param_5.516), '
           'custom_call_target="tpu_custom_call"')
GAT_AGG_T = ('%closed_call.201 = f32[60928,42]{1,0:T(8,128)} custom-call('
             's32[2261]{0:T(1024)S(1)} %dynamic-slice_reduce_fusion.36, '
             's32[2261,1,128]{2,1,0:T(1,128)S(1)} %dynamic-slice_bitcast_fusion.69, '
             'f32[2261,6,128]{2,1,0:T(8,128)} %copy_bitcast_fusion.8, '
             'f32[289408,42]{1,0:T(8,128)} %dynamic-slice_bitcast_fusion.70), '
             'custom_call_target="tpu_custom_call"')
GAT_SDDMM = ('%closed_call.202 = f32[2416,2,128]{2,1,0:T(2,128)} custom-call('
             's32[2416]{0:T(1024)S(1)} %dynamic-slice_reduce_fusion.37, '
             's32[2416,1,128]{2,1,0:T(1,128)S(1)} %bitcast.970, '
             'f32[60928,512]{1,0:T(8,128)} %bitcast.873, '
             'f32[309248,512]{1,0:T(8,128)} %dynamic-slice_bitcast_fusion.71), '
             'custom_call_target="tpu_custom_call"')
GAT_SDDMM_FUSED = ('%closed_call.40 = f32[4,2416,6,128]{3,2,1,0:T(8,128)} '
                   'fusion(f32[4,2416,6,128]{3,2,1,0:T(8,128)} %gte.1, '
                   's32[]{:T(128)} %gte.2, '
                   's32[2416]{0:T(1024)S(1)} %dynamic-slice_reduce_fusion.35, '
                   's32[2416,1,128]{2,1,0:T(1,128)S(1)} %bitcast.969, '
                   'f32[60928,42]{1,0:T(8,128)} %fusion.67, '
                   'f32[309248,42]{1,0:T(8,128)} %fusion.68), '
                   'kind=kCustom, calls=%fused_computation.40.clone')
SEGMENT_AGG = ('%closed_call.21 = f32[94208,256]{1,0:T(8,128)S(1)} custom-call('
               's32[12919]{0:T(1024)S(1)} %dynamic-slice_reduce_fusion.7, '
               's32[12919,1,128]{2,1,0:T(1,128)S(1)} %dynamic-slice_bitcast_fusion.20, '
               'f32[12919,1,128]{2,1,0:T(1,128)S(1)} %dynamic-slice_bitcast_fusion.21, '
               'f32[1653632,256]{1,0:T(8,128)} %dynamic-slice_bitcast_fusion.22, '
               'f32[94208,1]{1,0:T(8,128)} %copy.97), '
               'custom_call_target="tpu_custom_call"')
OPS = {GAT_AGG: "gat_agg", GAT_AGG_T: "gat_agg", GAT_SDDMM: "gat_sddmm",
       GAT_SDDMM_FUSED: "gat_sddmm", SEGMENT_AGG: "segment_agg"}


@pytest.mark.parametrize("text", list(OPS))
def test_each_kernel_file_claims_its_own_ops_only(text):
    owner = OPS[text]
    for name, mod in (("gat_agg", gat_agg), ("gat_sddmm", gat_sddmm),
                      ("segment_agg", segment_agg)):
        tag = short_name(text, {name: mod})
        assert tag.endswith(f" [{name}]" if name == owner else OTHER_KERNEL), (
            name, tag)
    # with every kernel file, in the order the trace tries them
    assert short_name(text).endswith(f" [{owner}]")
