"""The readers of the program's own host spans, on hand-built traces, and
one tiny traced CPU run of each cell: every span the program promises is
in the trace, and each compiled program carries its name."""
import re

import pytest

from perfbench import readers, spans
from perfbench.trace import Trace

MS = 1_000_000
SPAN_READERS = ("draw.epoch_ms", "draw.gather_share", "draw.stage_share",
                "draw.wait_share", "engine.dispatch_share")


def ctx(tr):
    return readers.Context(trace=tr, dev=None, chips=1, peaks={},
                           kind="sampled", dims=(1, 1), fanouts=(),
                           epochs=[], owned=[], halo=[], edges=[])


def read(name, tr):
    return readers.load_reader(name)(ctx(tr))


def draw(t0, gather=(2, 6), neighbors=(1, 2), stack=(8, 9)):
    """A worker draw 10 ms long from ``t0``: the samplers, one make_batch
    (1..7 ms) holding neighbour sampling and the gather, then the stack."""
    at = lambda a, b: (t0 + a * MS, t0 + b * MS)
    return [("eat.draw", *at(0, 10)),
            ("eat.draw.cbs", *at(0, 1)),
            ("eat.draw.make_batch", *at(1, 7)),
            ("eat.draw.neighbors", *at(*neighbors)),
            ("eat.draw.gather", *at(*gather)),
            ("eat.draw.stack", *at(*stack))]


def trace():
    # window 0..100 ms; whole draws at 20 and 50 ms, one cut at the start
    # (-5..5) and one at the end (95..105); the main thread dispatches and
    # waits while the worker draws
    host = [("bench.window", 0, 100 * MS)]
    for t0 in (-5, 20, 50, 95):
        host += draw(t0 * MS)
    host += [("eat.draw_wait", -3 * MS, 4 * MS),     # clipped to 0..4
             ("eat.draw_wait", 55 * MS, 62 * MS),     # the draw ends at 60
             ("eat.dispatch/phase0", 22 * MS, 25 * MS),
             ("eat.wait/phase0", 25 * MS, 40 * MS),
             ("eat.dispatch/eval-val-False", 24 * MS, 27 * MS),
             ("eat.dispatch/phase0", 98 * MS, 110 * MS)]  # clipped at 100
    return Trace(window=(0, 100 * MS), devices={0: []}, host=host)


def test_epoch_ms_leaves_out_draws_cut_by_the_window():
    assert read("draw.epoch_ms", trace()) == pytest.approx(10.0)
    tr = trace()
    tr.host.append(("eat.draw", 70 * MS, 90 * MS))
    assert read("draw.epoch_ms", tr) == pytest.approx(40 / 3)


def test_gather_share_of_the_whole_draws():
    # 4 of each 10 ms draw
    assert read("draw.gather_share", trace()) == pytest.approx(40.0)


def test_stage_share_subtracts_the_children_of_make_batch():
    # make_batch 6 ms less neighbours 1 and gather 4 = 1 ms, stack 1 ms
    assert read("draw.stage_share", trace()) == pytest.approx(20.0)
    tr = Trace(window=(0, 100 * MS), devices={},
               host=draw(10 * MS, gather=(2, 4), neighbors=(4, 5)))
    # self time 6 - 2 - 1 = 3 ms, stack 1 ms
    assert read("draw.stage_share", tr) == pytest.approx(40.0)


def test_gather_and_stage_shares_sum_within_the_draw():
    tr = trace()
    cbs = spans.within(spans.named(tr, "eat.draw.cbs"),
                       spans.whole(tr, spans.named(tr, "eat.draw")))
    total = (read("draw.gather_share", tr) + read("draw.stage_share", tr)
             + 100.0 * sum(e - s for s, e in cbs) / (20 * MS))
    # the rest of the draw is neighbour sampling (1 ms) and its gaps (2 ms)
    assert total == pytest.approx(70.0)


def test_main_thread_spans_overlapping_the_worker_are_not_children():
    tr = trace()
    # the main thread's dispatch lies inside the worker's make_batch in time
    tr.host.append(("eat.dispatch/phase0", 21 * MS + MS // 2, 22 * MS))
    assert read("draw.stage_share", tr) == pytest.approx(20.0)
    assert read("draw.gather_share", tr) == pytest.approx(40.0)


def test_wait_share_is_clipped_to_the_window():
    assert read("draw.wait_share", trace()) == pytest.approx(11.0)


def test_dispatch_share_is_the_union_over_programs_clipped():
    # 22..27 (two programs overlapping) and 98..100
    assert read("engine.dispatch_share", trace()) == pytest.approx(7.0)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_absent_spans_read_none(name):
    tr = Trace(window=(0, 100 * MS), devices={0: []},
               host=[("bench.window", 0, 100 * MS),
                     ("bench.draw_wait", 10 * MS, 20 * MS)])
    assert read(name, tr) is None
    # and spans wholly outside the window count as absent
    tr.host += [(n, s - 200 * MS, e - 200 * MS) for n, s, e in trace().host]
    assert read(name, tr) is None


# ------------------------------------------------ a tiny traced CPU run
ENGINE_SPANS = ("eat.dispatch/", "eat.wait/", "eat.compile/")
DRAW_SPANS = ("eat.draw", "eat.draw.cbs", "eat.draw.make_batch",
              "eat.draw.neighbors", "eat.draw.gather", "eat.draw.stack",
              "eat.draw_wait")


@pytest.fixture(scope="module", params=["products-sampled",
                                        "flickr-fullgraph"])
def traced(request, tiny_root):
    """Three epochs of a cell inside a ``bench.window`` span under the
    profiler, from the engine's first compile on."""
    from jax.profiler import TraceAnnotation

    from perfbench import harness
    from perfbench.trace import capture

    cell = harness.Cell(harness.load_cell(request.param, tiny_root))
    trainer = harness.Trainer(cell, seed=2**32 + 3)

    def window():
        with TraceAnnotation("bench.window"):
            return [trainer.epoch() for _ in range(3)]

    try:
        epochs, tr = capture(window)
        yield cell, epochs, tr
    finally:
        trainer.close()
        cell.close()


def test_every_span_of_the_table_is_traced(traced):
    cell, _, tr = traced
    names = {n for n, _, _ in tr.host if n.startswith("eat.")}
    programs = {key[0] for key in cell.engine._cache}
    for prefix in ENGINE_SPANS:
        assert {prefix + p for p in programs} <= names, prefix
    expect = set(DRAW_SPANS) if cell.kind == "sampled" else set()
    assert {n for n in names if not n.startswith(ENGINE_SPANS)} == expect
    if cell.kind == "sampled":
        assert "phase0" in programs
    else:
        assert "phase0_fg-1" in programs


def test_span_readers_read_the_traced_run(traced):
    cell, epochs, tr = traced
    c = ctx(tr)
    c.kind, c.epochs = cell.kind, epochs
    got = {n: readers.load_reader(n)(c) for n in SPAN_READERS}
    assert 0 < got["engine.dispatch_share"] <= 100
    if cell.kind != "sampled":
        assert {n for n, v in got.items() if v is not None} == {
            "engine.dispatch_share"}
        return
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["draw.gather_share"] + got["draw.stage_share"] <= 100
    draws = spans.whole(tr, spans.named(tr, "eat.draw"))
    children = sum(e - s for n in ("eat.draw.cbs", "eat.draw.make_batch",
                                   "eat.draw.stack")
                   for s, e in spans.within(spans.named(tr, n), draws))
    # the children account for the draw
    total = sum(e - s for s, e in draws)
    assert 0.9 * total <= children <= total


def test_compiled_programs_carry_their_names(traced):
    cell, _, _ = traced
    for key, exe in cell.engine._cache.items():
        module = exe.as_text().split(",", 1)[0]
        assert module == "HloModule jit_eat_" + re.sub(r"\W", "_", key[0])
