"""The program's ``segment_agg`` Pallas kernel (``kernels/segment_agg.py``):
the mean aggregation of Eq. 1 over a block's CSR edges, cut into 128-edge
chunks.  What the benchmark knows of it, found by the name
``perfbench/kernels/segment_agg.py``:

  ``matches``     the operand layout that tags its op in a trace;
  ``agg_flops``, ``agg_bytes``
                  operations and bytes of one call;
  ``calls``       the calls a window made on one chip, from the cell's
                  layer widths (the model module's ``layer_dims``).

One call over ``E`` real edges into ``rows`` destination rows of width
``D`` needs ``E * D`` adds, reads the ``E`` source rows and the edge
indices (source and destination, int32) and writes the destination rows;
this is so whatever implements the aggregation (an XLA gather feeding the
kernel, or a kernel that gathers itself).  Padded edge chunks, halo rows
and padded rows do not count.
"""
from __future__ import annotations

import re

from perfbench.trace import tagged

__all__ = ["matches", "is_segment_agg", "agg_flops", "agg_bytes",
           "eval_agg_calls", "fullgraph_agg_calls", "calls"]

INDEX_BYTES = 4
# the kernel's operands: its chunk->row-block map s32[C], then per chunk
# 128 edge ids s32[C,1,128] and 128 edge weights f32[C,1,128]
_CHUNKS = re.compile(r"s32\[(\d+)\] %[\w.\-]+, s32\[\1,1,128\] %[\w.\-]+, "
                     r"(?:f32|bf16)\[\1,1,128\] %")


def matches(hlo: str) -> bool:
    """Whether a Pallas op's HLO text (layouts stripped) is this kernel."""
    return _CHUNKS.search(hlo) is not None


def is_segment_agg(name: str) -> bool:
    """Whether a traced op's name carries this kernel's tag."""
    return tagged(name, "segment_agg")


def agg_flops(edges: int, width: int) -> float:
    return float(edges) * width


def agg_bytes(edges: int, rows: int, width: int, itemsize: int = 4) -> float:
    return (float(edges) * width * itemsize + 2.0 * edges * INDEX_BYTES
            + float(rows) * width * itemsize)


def eval_agg_calls(dims, owned: int, edges: int) -> list[tuple[int, int, int]]:
    """``(edges, rows, width)`` of each aggregation of one partition's
    evaluation forward: one per layer, at that layer's input width."""
    return [(edges, owned, d) for d in dims[:-1]]


def fullgraph_agg_calls(dims, owned: int, halo: int,
                        edges: int) -> list[tuple[int, int, int]]:
    """Aggregations of one partition's full-graph training step: the
    forward of every layer, and the transpose (into owned and halo source
    rows) of every layer whose input depends on the weights (all but the
    first, whose input is the features)."""
    fwd = [(edges, owned, d) for d in dims[:-1]]
    bwd = [(edges, owned + halo, d) for d in dims[1:-1]]
    return fwd + bwd


def calls(ctx) -> list[tuple[int, int, int]]:
    """(edges, rows, width) of every aggregation the window ran on the
    chip ``ctx`` reads, over the partitions that chip holds: each epoch's
    evaluation forward, and in full-graph cells each training step's
    forward and transpose."""
    out = []
    for p in ctx.parts_on_dev():
        ev = eval_agg_calls(ctx.dims, ctx.owned[p], ctx.edges[p])
        tr = (fullgraph_agg_calls(ctx.dims, ctx.owned[p], ctx.halo[p],
                                  ctx.edges[p])
              if ctx.kind == "fullgraph" else [])
        for e in ctx.epochs:
            out += ev + tr * e.steps
    return out
