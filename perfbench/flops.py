"""Operations and bytes of the work a window did, from shapes and counts.

Only real work counts: real edges, real owned nodes, real (unmasked) seeds.
Padded rows, halo rows, padded edge chunks and recomputation do not.

``segment_agg`` (the mean aggregation of Eq. 1 over a call's real edges
``E`` into ``rows`` destination rows of width ``D``) needs ``E * D`` adds
and reads the ``E`` source rows and the edge indices (source and
destination, int32) and writes the destination rows; this is so whatever
implements the aggregation (an XLA gather feeding the kernel, or a kernel
that gathers itself).

A training step is its forward's dense self/neighbour matmuls and
aggregation adds, and a backward at twice the forward.
"""
from __future__ import annotations

__all__ = ["agg_flops", "agg_bytes", "sampled_seed_flops",
           "fullgraph_step_flops", "fullgraph_agg_calls",
           "eval_agg_calls"]

INDEX_BYTES = 4


def agg_flops(edges: int, width: int) -> float:
    return float(edges) * width


def agg_bytes(edges: int, rows: int, width: int, itemsize: int = 4) -> float:
    return (float(edges) * width * itemsize + 2.0 * edges * INDEX_BYTES
            + float(rows) * width * itemsize)


def sampled_seed_flops(dims, fanouts) -> float:
    """Forward FLOPs per real seed of the 2-layer sampled GraphSAGE:
    layer 1 on the target and on its ``F1`` sampled neighbours (each with
    its ``F2`` samples aggregated), layer 2 on the target."""
    d, h, c = dims
    f1, f2 = fanouts
    layer1_target = 4.0 * d * h + f1 * d
    layer1_hop = f1 * (4.0 * d * h + f2 * d)
    layer2 = 4.0 * h * c + f1 * h
    return layer1_target + layer1_hop + layer2


def fullgraph_step_flops(dims, owned: int, edges: int) -> float:
    """Forward FLOPs of one partition's full-graph step: per layer the
    self and neighbour matmuls over its owned rows and the aggregation
    adds over its real edges."""
    return sum(4.0 * owned * d_in * d_out + float(edges) * d_in
               for d_in, d_out in zip(dims[:-1], dims[1:]))


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
