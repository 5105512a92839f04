"""GraphSAGE (Hamilton et al. 2017) in pure JAX — the paper's model (§II).

Eq. 1–2 with the mean aggregator:

    h_N(v) = mean(h_u, u in sampled N(v))
    h_v    = sigma(W · concat(h_N(v), h_v))

Two apply paths, ONE aggregation op:
  · ``apply_sampled`` — fixed-shape minibatch blocks from NeighborSampler
    (the DistDGL training path, 2 layers as the paper fixes).
  · ``apply_full``    — full-graph forward over edge lists (evaluation,
    centralized baseline AND full-graph training; this is the compute
    hot-spot the Pallas ``segment_agg`` kernel accelerates).

Both route Eq. 1's neighbour mean through :meth:`GraphSAGE.neighbor_mean`:
irregular CSR aggregation goes to the differentiable blocked Pallas op
``kernels.ops.segment_mean_op`` (custom VJP — ``jax.grad`` stages the
transpose kernel, DESIGN.md §6), while the sampled path's fixed-fanout
blocks are the regular degenerate case where the one-hot × matmul collapses
to a dense ``mean(axis)``.  The old per-call-site ``segment_agg=`` callback
plumbing is gone.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SAGEParams", "GraphSAGE"]


class SAGELayer(NamedTuple):
    w_self: jnp.ndarray   # (d_in, d_out)
    w_neigh: jnp.ndarray  # (d_in, d_out)
    b: jnp.ndarray        # (d_out,)


class SAGEParams(NamedTuple):
    """Stack of SAGE layers (any depth >= 1), one pytree.

    ``layer1``/``layer2`` are views kept for the fixed-two-layer call
    sites (the sampled training path and its tests): first and LAST
    layer respectively, which coincides with the old fields at depth 2.
    """

    layers: tuple[SAGELayer, ...]

    @property
    def layer1(self) -> SAGELayer:
        return self.layers[0]

    @property
    def layer2(self) -> SAGELayer:
        return self.layers[-1]


def _glorot(rng: np.random.Generator, shape: tuple[int, ...]) -> jnp.ndarray:
    fan_in, fan_out = shape[0], shape[-1]
    scale = np.sqrt(6.0 / (fan_in + fan_out))
    return jnp.asarray(rng.uniform(-scale, scale, size=shape), dtype=jnp.float32)


@dataclass(frozen=True)
class GraphSAGE:
    """Config + functional apply (params are explicit pytrees)."""

    feature_dim: int
    hidden_dim: int
    num_classes: int
    num_layers: int = 2
    l2_normalize: bool = False
    dropout: float = 0.0  # applied to inputs of each layer when training

    # the engine's aggregation structures for this model: the mean kernel's
    aggregation = "mean"

    @property
    def layer_dims(self) -> tuple[int, ...]:
        """Per-layer (input, ..., output) widths: (D, H, ..., H, C)."""
        return ((self.feature_dim,)
                + (self.hidden_dim,) * (self.num_layers - 1)
                + (self.num_classes,))

    @property
    def layer_input_dims(self) -> tuple[int, ...]:
        """Width of the embedding each layer's halo exchange ships."""
        return self.layer_dims[:-1]

    # ---------------------------------------------------------------- init
    def init(self, seed: int = 0) -> SAGEParams:
        if self.num_layers < 1:
            raise ValueError(f"num_layers must be >= 1, got {self.num_layers}")
        rng = np.random.default_rng([seed, 0x5A6E])
        dims = self.layer_dims

        def layer(d_in: int, d_out: int) -> SAGELayer:
            return SAGELayer(
                w_self=_glorot(rng, (d_in, d_out)),
                w_neigh=_glorot(rng, (d_in, d_out)),
                b=jnp.zeros((d_out,), jnp.float32),
            )

        return SAGEParams(layers=tuple(
            layer(dims[i], dims[i + 1]) for i in range(self.num_layers)))

    # ------------------------------------------------------------- helpers
    def _layer(self, lp: SAGELayer, h_self: jnp.ndarray, h_neigh: jnp.ndarray,
               activate: bool) -> jnp.ndarray:
        out = h_self @ lp.w_self + h_neigh @ lp.w_neigh + lp.b
        if activate:
            out = jax.nn.relu(out)
            if self.l2_normalize:
                out = out / jnp.maximum(jnp.linalg.norm(out, axis=-1, keepdims=True), 1e-9)
        return out

    def layer(self, lp: SAGELayer, h: jnp.ndarray, shard: dict, agg, i: int,
              last: bool) -> jnp.ndarray:
        """Layer ``i`` of the distributed full-graph forward over a
        partition's rows: ``agg(h, shard)`` is the neighbour mean."""
        a = agg(h, shard)
        h = h @ lp.w_self + a @ lp.w_neigh + lp.b
        if not last:
            h = jax.nn.relu(h)
        return h

    def _maybe_dropout(self, x: jnp.ndarray, key) -> jnp.ndarray:
        if self.dropout <= 0.0 or key is None:
            return x
        keep = 1.0 - self.dropout
        mask = jax.random.bernoulli(key, keep, x.shape)
        return jnp.where(mask, x / keep, 0.0)

    # --------------------------------------------------- the aggregation op
    @staticmethod
    def neighbor_mean(x: jnp.ndarray, *, axis: int | None = None,
                      blocks: dict | None = None, num_rows: int | None = None,
                      row_base=0) -> jnp.ndarray:
        """Eq. 1's neighbour mean — the model's single aggregation entry.

        ``blocks`` (from ``kernels.ops.build_vjp_blocks``) selects the
        irregular CSR path: the differentiable blocked Pallas op
        ``segment_mean_op`` (forward AND backward on the MXU).  ``axis``
        selects the sampled path's fixed-fanout blocks — the regular
        degenerate case (every row has exactly ``fanout`` neighbours, so the
        one-hot × matmul collapses to a dense mean along that axis).
        """
        if blocks is not None:
            from ..kernels.ops import segment_mean_op
            return segment_mean_op(x, blocks, num_rows=num_rows,
                                   row_base=row_base)
        return x.mean(axis=axis)

    # ------------------------------------------------------- sampled apply
    def apply_sampled(
        self,
        params: SAGEParams,
        x_t: jnp.ndarray,   # (B, D) target features
        x_1: jnp.ndarray,   # (B, F1, D) their sampled neighbours
        x_2: jnp.ndarray,   # (B, F1, F2, D) second-hop samples
        dropout_key=None,
    ) -> jnp.ndarray:
        """Two-layer sampled forward -> (B, num_classes) logits."""
        if self.num_layers != 2:
            raise ValueError(
                "apply_sampled is the paper's fixed two-layer fanout path; "
                f"got num_layers={self.num_layers}")
        k1 = k2 = None
        if dropout_key is not None:
            k1, k2 = jax.random.split(dropout_key)
        x_t = self._maybe_dropout(x_t, k1)

        # layer 1 for targets: aggregate their 1-hop samples
        h1_t = self._layer(params.layer1, x_t,
                           self.neighbor_mean(x_1, axis=1), activate=True)
        # layer 1 for 1-hop nodes: aggregate the 2-hop samples
        h1_1 = self._layer(params.layer1, x_1,
                           self.neighbor_mean(x_2, axis=2), activate=True)
        h1_1 = self._maybe_dropout(h1_1, k2)
        # layer 2 for targets
        logits = self._layer(params.layer2, h1_t,
                             self.neighbor_mean(h1_1, axis=1), activate=False)
        return logits

    # ---------------------------------------------------------- full apply
    def apply_full(
        self,
        params: SAGEParams,
        features: jnp.ndarray,     # (N, D)
        edge_src: jnp.ndarray,     # (E,) message sources
        edge_dst: jnp.ndarray,     # (E,) message destinations
        num_nodes: int,
        *,
        blocks: dict | None = None,   # prebuilt ops.build_vjp_blocks arrays
        use_pallas: bool = True,
    ) -> jnp.ndarray:
        """Full-graph n-layer forward -> (N, num_classes) logits.

        Differentiable end-to-end: the Pallas path (default) goes through
        the custom-VJP ``segment_mean_op``, the ``use_pallas=False`` path
        through the canonical jnp reference ``kernels.ref.segment_agg_ref``
        — the same two backends every other forward consumes.  ``blocks``
        may be passed prebuilt; otherwise it is built host-side from the
        edge lists, which requires them CONCRETE: traced edges without
        ``blocks`` raise instead of silently switching backends (build the
        blocks outside ``jit``, or pass ``use_pallas=False``).
        """
        if use_pallas and blocks is None and any(
                isinstance(e, jax.core.Tracer) for e in (edge_src, edge_dst)):
            raise ValueError(
                "apply_full's Pallas path builds its block structure on the "
                "host and needs concrete edge lists; under jit pass prebuilt "
                "blocks (kernels.ops.build_vjp_blocks) or use_pallas=False")
        if use_pallas:
            if blocks is None:
                from ..kernels.ops import build_vjp_blocks
                blocks = build_vjp_blocks(np.asarray(edge_src),
                                          np.asarray(edge_dst),
                                          num_rows=num_nodes,
                                          num_src_rows=num_nodes)
            mean_agg = lambda h: self.neighbor_mean(
                h, blocks=blocks, num_rows=num_nodes)
        else:
            from ..kernels.ref import segment_agg_ref
            mean_agg = lambda h: segment_agg_ref(
                h, edge_src, edge_dst, num_nodes, mean=True)

        h = features
        last = len(params.layers) - 1
        for i, lp in enumerate(params.layers):
            h = self._layer(lp, h, mean_agg(h), activate=i < last)
        return h

    # ------------------------------------------------------------ loss fns
    def make_loss_fn(self, loss="ce", focal_gamma: float = 2.0):
        """loss_fn(params, batch) for the GP trainer.  batch = dict with
        x_t, x_1, x_2, labels (and optional mask for padded batches)."""
        from ..train.losses import cross_entropy_loss, focal_loss

        def loss_fn(params: SAGEParams, batch: dict[str, Any]) -> jnp.ndarray:
            logits = self.apply_sampled(params, batch["x_t"], batch["x_1"], batch["x_2"])
            mask = batch.get("mask")
            if loss == "focal":
                return focal_loss(logits, batch["labels"], gamma=focal_gamma, mask=mask)
            return cross_entropy_loss(logits, batch["labels"], mask=mask)

        return loss_fn
