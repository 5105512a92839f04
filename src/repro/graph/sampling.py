"""Fixed-shape GraphSAGE neighbour sampling (paper fanout (25, 25)).

DistDGL samples neighbourhoods on CPU workers and ships blocks to trainers;
we do the same: NumPy sampling here, fixed-shape index blocks into the jitted
model.  Sampling WITH replacement gives static shapes (a TPU requirement —
this is part of the GPU->TPU adaptation documented in DESIGN.md §2):

    targets      (B,)
    nbrs1        (B, F1)          neighbours of targets
    nbrs2        (B*F1, F2)       neighbours of nbrs1

Isolated nodes self-loop, matching DGL's `add_self_loop` fallback.

The feature rows of a batch are gathered on the device when the caller hands
``feature_views`` the sampler's own graph table: the sampler stages one copy
of it on the default device the first time a draw needs it, and only the
int32 ids cross to the device.  Any other table is gathered here in numpy.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from .csr import CSRGraph

__all__ = ["SampledBlocks", "NeighborSampler"]


@jax.jit
def eat_gather(table, targets, nbrs1, nbrs2):
    """x_t (B,D), x_1 (B,F1,D), x_2 (B,F1,F2,D) from a device-resident table
    (the compiled module reads ``jit_eat_gather``)."""
    b, f1 = nbrs1.shape
    return (table[targets], table[nbrs1],
            table[nbrs2].reshape(b, f1, nbrs2.shape[1], -1))


@dataclass
class SampledBlocks:
    """One minibatch of sampled computation blocks (all global node ids)."""

    targets: np.ndarray            # (B,)
    nbrs1: np.ndarray              # (B, F1)
    nbrs2: np.ndarray              # (B*F1, F2)
    # the sampler that drew the blocks; it serves the device gather
    sampler: NeighborSampler | None = field(default=None, repr=False,
                                            compare=False)

    def feature_views(self, features: np.ndarray):
        """Gather features: x_t (B,D), x_1 (B,F1,D), x_2 (B,F1,F2,D).

        On the device, as jax arrays, when ``features`` is the drawing
        sampler's own graph table (the same object) and the sampler stages
        it; in numpy for any other table.  The rows are the same either way.
        """
        b, f1 = self.nbrs1.shape
        f2 = self.nbrs2.shape[1]
        with TraceAnnotation("eat.draw.gather") as span:
            table = (self.sampler.device_table(features)
                     if self.sampler is not None else None)
            span.set_metadata(rows=b * (1 + f1 + f1 * f2),
                              device=int(table is not None))
            if table is not None:
                return eat_gather(table,
                                  *(np.asarray(ids, np.int32) for ids in
                                    (self.targets, self.nbrs1, self.nbrs2)))
            x_t = features[self.targets]
            x_1 = features[self.nbrs1.reshape(-1)].reshape(b, f1, -1)
            x_2 = features[self.nbrs2.reshape(-1)].reshape(b, f1, f2, -1)
        return x_t, x_1, x_2


class NeighborSampler:
    """Uniform-with-replacement fanout sampler over a CSR graph.

    ``stage_features=False`` keeps the graph's feature table off the device
    (the feature store's host gather)."""

    def __init__(self, graph: CSRGraph, fanouts: tuple[int, int] = (25, 25),
                 seed: int = 0, stage_features: bool = True):
        self.graph = graph
        self.fanouts = fanouts
        self._rng = np.random.default_rng([seed, 0xAB1E])
        self.stage_features = stage_features
        self.table = None              # the device copy, staged on first use

    def device_table(self, features: np.ndarray):
        """The device copy of the graph's feature table when ``features`` is
        that table and the sampler stages it (once, on first use); else
        None."""
        if not self.stage_features or features is not self.graph.features:
            return None
        if self.table is None:
            self.table = jax.device_put(features)
        return self.table

    def _sample_neighbors(self, nodes: np.ndarray, fanout: int) -> np.ndarray:
        g = self.graph
        deg = g.indptr[nodes + 1] - g.indptr[nodes]
        out = np.empty((len(nodes), fanout), dtype=np.int64)
        r = self._rng.integers(0, 1 << 62, size=(len(nodes), fanout))
        has = deg > 0
        # vectorised modular pick into each node's CSR span
        offs = (r[has] % deg[has, None]) + g.indptr[nodes[has], None]
        out[has] = g.indices[offs]
        out[~has] = nodes[~has, None]  # isolated -> self loop
        return out

    def sample(self, targets: np.ndarray) -> SampledBlocks:
        targets = np.asarray(targets, dtype=np.int64)
        f1, f2 = self.fanouts
        with TraceAnnotation("eat.draw.neighbors"):
            nbrs1 = self._sample_neighbors(targets, f1)
            nbrs2 = self._sample_neighbors(nbrs1.reshape(-1), f2)
        return SampledBlocks(targets=targets, nbrs1=nbrs1, nbrs2=nbrs2,
                             sampler=self)
