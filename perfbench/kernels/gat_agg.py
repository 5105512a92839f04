"""The program's ``gat_agg`` Pallas kernel (``kernels/gat_agg.py``): GAT's
attention-weighted sum of a block's in-edge messages, per head, cut into
128-edge chunks; it runs the forward aggregation of each layer and, over
the CSC mirror, its transpose.  What the benchmark knows of it, found by
the name ``perfbench/kernels/gat_agg.py``:

  ``matches``     the operand layout that tags its op in a trace;
  ``agg_flops``, ``agg_bytes``
                  operations and bytes of one call;
  ``calls``       the calls a window made on one chip, from the cell's
                  layer widths (the model module's ``layer_dims``) and its
                  heads (the model module's ``HEADS``).

One call takes the heads of one group (:func:`head_groups`): the program
splits a layer's heads into calls of at most :data:`GROUP_LANES` message
lanes, two heads of 256 a call on the hidden layers.  One call over ``E``
real edges into ``rows`` rows of width ``W`` (the group's heads) needs a
multiply and an add per edge and element, reads the ``E`` message rows,
the edge indices (source and destination, int32) and the ``E * K``
weights of all the layer's ``K`` heads (each call reads every head's
weights of its chunk), and writes the rows.  Padded edge chunks, halo rows
and padded rows do not count.
"""
from __future__ import annotations

import re

__all__ = ["GROUP_LANES", "matches", "head_groups", "agg_flops", "agg_bytes",
           "layer_calls", "eval_calls", "fullgraph_calls", "calls"]

INDEX_BYTES = 4
# the widest group of message lanes one call of the program's kernel takes
GROUP_LANES = 512
# the kernel's operands: its chunk->row-block map s32[C], then per chunk
# 128 destination rows s32[C,1,128] and per head 128 edge weights
# f32[C,K,128], K > 1 (the mean kernel's mask is f32[C,1,128])
_CHUNKS = re.compile(r"s32\[(\d+)\] %[\w.\-]+, s32\[\1,1,128\] %[\w.\-]+, "
                     r"(?:f32|bf16)\[\1,(?:[2-9]|\d\d+),128\] %")


def matches(hlo: str) -> bool:
    """Whether a Pallas op's HLO text (layouts stripped) is this kernel."""
    return _CHUNKS.search(hlo) is not None


def head_groups(width: int, heads: int) -> list[int]:
    """The heads of each call for a layer of ``heads`` heads whose
    messages are ``width`` wide: as many whole heads as fit
    :data:`GROUP_LANES` lanes a call."""
    g = max(1, min(heads, GROUP_LANES // (width // heads)))
    return [min(g, heads - h) for h in range(0, heads, g)]


def agg_flops(edges: int, width: int) -> float:
    return 2.0 * edges * width


def agg_bytes(edges: int, rows: int, width: int, heads: int,
              itemsize: int = 4) -> float:
    """One call at the group width ``width``; ``heads`` is the layer's
    head count, whose weights the call reads."""
    return (float(edges) * width * itemsize + 2.0 * edges * INDEX_BYTES
            + float(edges) * heads * itemsize
            + float(rows) * width * itemsize)


def layer_calls(edges: int, rows: int, width: int,
                heads: int) -> list[tuple]:
    """``(edges, rows, group width, heads)`` of each call of one
    aggregation of a layer whose messages are ``width`` wide."""
    f = width // heads
    return [(edges, rows, g * f, heads) for g in head_groups(width, heads)]


def eval_calls(dims, heads, owned: int, edges: int) -> list[tuple]:
    """The calls of one partition's evaluation forward: each layer's
    aggregation at its message width."""
    return [c for w, k in zip(dims[1:], heads)
            for c in layer_calls(edges, owned, w, k)]


def fullgraph_calls(dims, heads, owned: int, halo: int,
                    edges: int) -> list[tuple]:
    """The calls of one partition's full-graph training step, per layer:
    the forward; its recomputation in the backward pass (every layer is
    rematerialised), but for the last layer, whose aggregate its gradient
    does not read, so the compiler drops it; and the transpose into owned
    and halo source rows (every layer's messages depend on its weights)."""
    last = len(heads) - 1
    out = []
    for i, (w, k) in enumerate(zip(dims[1:], heads)):
        fwd = layer_calls(edges, owned, w, k)
        out += fwd * (1 if i == last else 2)
        out += layer_calls(edges, owned + halo, w, k)
    return out


def calls(ctx) -> list[tuple]:
    """(edges, rows, width, heads) of every call the window ran on the chip
    ``ctx`` reads, over the partitions that chip holds: each epoch's
    evaluation forward and each training step's calls."""
    heads = ctx.model.HEADS
    out = []
    for p in ctx.parts_on_dev():
        ev = eval_calls(ctx.dims, heads, ctx.owned[p], ctx.edges[p])
        tr = fullgraph_calls(ctx.dims, heads, ctx.owned[p], ctx.halo[p],
                             ctx.edges[p])
        for e in ctx.epochs:
            out += ev + tr * e.steps
    return out
