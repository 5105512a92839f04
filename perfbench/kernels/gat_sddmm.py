"""The program's ``gat_sddmm`` Pallas kernel (``kernels/gat_agg.py``): the
gradient of GAT's attention weights, one dot product per edge and head of
the destination's cotangent row with the source's message row, over a
block's 128-edge chunks.  What the benchmark knows of it, found by the
name ``perfbench/kernels/gat_sddmm.py``: ``matches``, ``sddmm_flops``,
``sddmm_bytes`` and ``calls``, as ``kernels/gat_agg.py`` has them.

It runs once per head group (``kernels/gat_agg.py``'s ``head_groups``)
of each layer of a training step.  One call over ``E`` real edges into
``rows`` destination rows of width ``W`` (``K`` heads, the group's) needs
a multiply and an add per edge and element, reads the ``E`` message rows,
the rows' cotangents once and the edge indices, and writes ``E * K``
weights' gradients.  Padded edge chunks and rows do not count.
"""
from __future__ import annotations

import re
from pathlib import Path

from perfbench import byname

__all__ = ["matches", "sddmm_flops", "sddmm_bytes", "step_calls", "calls"]

INDEX_BYTES = 4
# the kernel's operands: its chunk->row-block map s32[C], per chunk 128
# destination rows s32[C,1,128], then the cotangent rows f32[R,W] and the
# message rows f32[M,W] (the aggregation kernels take a per-chunk weight
# f32[C,K,128] third)
_CHUNKS = re.compile(r"s32\[(\d+)\] %[\w.\-]+, s32\[\1,1,128\] %[\w.\-]+, "
                     r"(?:f32|bf16)\[\d+,(\d+)\] %[\w.\-]+, "
                     r"(?:f32|bf16)\[\d+,\2\] %")
_AGG = byname.load("kernel", "gat_agg", Path(__file__).resolve().parent)


def matches(hlo: str) -> bool:
    """Whether a Pallas op's HLO text (layouts stripped) is this kernel."""
    return _CHUNKS.search(hlo) is not None


def sddmm_flops(edges: int, width: int) -> float:
    return 2.0 * edges * width


def sddmm_bytes(edges: int, rows: int, width: int, heads: int,
                itemsize: int = 4) -> float:
    return (float(edges) * width * itemsize + float(rows) * width * itemsize
            + 2.0 * edges * INDEX_BYTES + float(edges) * heads * itemsize)


def step_calls(dims, heads, owned: int, edges: int) -> list[tuple]:
    """``(edges, rows, group width, group heads)`` of each call of one
    partition's training step: one per head group of every layer."""
    out = []
    for w, k in zip(dims[1:], heads):
        f = w // k
        out += [(edges, owned, g * f, g) for g in _AGG.head_groups(w, k)]
    return out


def calls(ctx) -> list[tuple]:
    """(edges, rows, width, heads) of every call the window ran on the chip
    ``ctx`` reads, over the partitions that chip holds."""
    out = []
    for p in ctx.parts_on_dev():
        step = step_calls(ctx.dims, ctx.model.HEADS, ctx.owned[p],
                          ctx.edges[p])
        for e in ctx.epochs:
            out += step * e.steps
    return out
