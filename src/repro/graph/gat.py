"""GAT (Veličković et al., "Graph Attention Networks", ICLR 2018, arXiv
1710.10903) in pure JAX, on the engine's full-graph path.

One layer with ``K`` heads of width ``F`` and input ``h``:

    z      = h @ W                                  (N, K*F), heads contiguous
    e[u→v] = LeakyReLU_0.2(<a_src[k], z[u,k]> + <a_dst[k], z[v,k]>)
             for u in the in-neighbours of v, and v itself once
    α[u→v] = softmax over u of e[·→v], per v and head k
    o[v,k] = Σ_u α[u→v,k] · z[u,k]

Hidden layers concatenate their heads, add the bias and apply ELU; a hidden
layer after the first adds its input as an identity skip (DGL's ``GATConv``
residual where the widths agree: the paper's skip across the intermediate
PPI layer).  The last layer averages its heads and adds the bias.  The
paper's inductive (PPI) layout is :data:`PPI_HEADS` heads of
:data:`PPI_HEAD_DIM`; no dropout and no L2, as the paper trains PPI.

The aggregation of a layer is the attention op of ``kernels/gat_agg.py``
(the ``gat_agg`` kernel forward, the transpose and the ``gat_sddmm`` kernel
backward) or, on the engine's ``use_pallas_agg=False`` backend, its
``jax.numpy`` segment-op spelling.  There is no sampled path: GAT trains
and evaluates through the distributed full-graph forward
(``full_graph_train``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["GAT", "GATLayer", "GATParams", "PPI_HEADS", "PPI_HEAD_DIM"]

PPI_HEADS = (4, 4, 6)      # the paper's §3.3 inductive configuration
PPI_HEAD_DIM = 256


class GATLayer(NamedTuple):
    w: jnp.ndarray        # (d_in, K * F)
    a_src: jnp.ndarray    # (K, F)
    a_dst: jnp.ndarray    # (K, F)
    b: jnp.ndarray        # (K * F,) on hidden layers, (C,) on the last


class GATParams(NamedTuple):
    layers: tuple[GATLayer, ...]


def _glorot(rng: np.random.Generator, shape: tuple[int, ...],
            fan_in: int, fan_out: int) -> jnp.ndarray:
    scale = np.sqrt(6.0 / (fan_in + fan_out))
    return jnp.asarray(rng.uniform(-scale, scale, size=shape), jnp.float32)


def _no_sampled_path(what: str) -> ValueError:
    return ValueError(
        f"GAT has no {what}: it trains and evaluates on the full-graph path "
        f"only (full_graph_train, the distributed full-graph forward)")


@dataclass(frozen=True)
class GAT:
    """Config + functional apply (params are explicit pytrees)."""

    feature_dim: int
    num_classes: int
    heads: tuple[int, ...] = PPI_HEADS
    head_dim: int = PPI_HEAD_DIM

    # the engine builds the attention's structures for this model
    aggregation = "attention"

    def __post_init__(self):
        if len(self.heads) < 1:
            raise ValueError("GAT needs at least one layer")
        widths = {k * self.head_dim for k in self.heads[:-1]}
        if len(self.heads) > 2 and len(widths) > 1:
            raise ValueError(
                f"the identity skip needs equal hidden widths; heads "
                f"{self.heads} at width {self.head_dim} give {sorted(widths)}")

    @property
    def num_layers(self) -> int:
        return len(self.heads)

    def head_widths(self) -> tuple[int, ...]:
        """Per layer, the width of one head: ``head_dim`` on hidden layers,
        the class count on the last."""
        return (self.head_dim,) * (self.num_layers - 1) + (self.num_classes,)

    @property
    def layer_dims(self) -> tuple[int, ...]:
        """(D, K1*F, ..., K_last*C): the widths messages travel at."""
        return (self.feature_dim,) + tuple(
            k * f for k, f in zip(self.heads, self.head_widths()))

    @property
    def layer_input_dims(self) -> tuple[int, ...]:
        """Width of the embedding each layer's halo exchange ships."""
        return self.layer_dims[:-1]

    # ---------------------------------------------------------------- init
    def init(self, seed: int = 0) -> GATParams:
        """Glorot-uniform weights and attention vectors, zero biases."""
        rng = np.random.default_rng([seed, 0x6A7])
        dims = self.layer_dims
        layers = []
        for i, (k, f) in enumerate(zip(self.heads, self.head_widths())):
            last = i == self.num_layers - 1
            layers.append(GATLayer(
                w=_glorot(rng, (dims[i], k * f), dims[i], k * f),
                a_src=_glorot(rng, (k, f), f, 1),
                a_dst=_glorot(rng, (k, f), f, 1),
                b=jnp.zeros((f if last else k * f,), jnp.float32)))
        return GATParams(layers=tuple(layers))

    # --------------------------------------------------------------- layer
    def layer(self, lp: GATLayer, h: jnp.ndarray, shard: dict, agg, i: int,
              last: bool) -> jnp.ndarray:
        """Layer ``i`` over a partition's rows: project, score, normalise
        and aggregate (``agg(z, s_src, s_dst, shard)``, the edge softmax
        and the weighted sum with each row's self term), then the bias, the
        skip and ELU, or on the last layer the mean over heads.

        The layer is rematerialised (``jax.checkpoint``): its backward
        recomputes the projection, the softmax and the forward aggregation
        from the layer's input rather than keeping them, which on Flickr at
        the PPI widths takes a training step's device peak from about 14.5
        to about 10.8 GiB (TPU v5e compiler's memory analysis)."""
        def attend(lp, h):
            n = h.shape[0]
            k = lp.a_src.shape[0]
            z = h @ lp.w
            zh = z.reshape(n, k, -1)
            s_src = (zh * lp.a_src).sum(-1)
            s_dst = (zh * lp.a_dst).sum(-1)
            o = agg(z, s_src, s_dst, shard)
            if last:
                return o.reshape(n, k, -1).mean(axis=1) + lp.b
            out = o + lp.b
            if i > 0:
                out = out + h
            return jax.nn.elu(out)

        return jax.checkpoint(attend)(lp, h)

    # ---------------------------------------------------------- full apply
    def apply_full(self, params: GATParams, features: jnp.ndarray,
                   edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
                   num_nodes: int, *, blocks: dict | None = None
                   ) -> jnp.ndarray:
        """Whole-graph forward through the Pallas kernels -> (N,
        num_classes) logits; ``blocks`` from ``kernels.gat_agg.
        build_gat_blocks``, built here from concrete edges when absent."""
        from ..kernels.gat_agg import attention_agg_op, build_gat_blocks

        if blocks is None:
            blocks = build_gat_blocks(np.asarray(edge_src),
                                      np.asarray(edge_dst), num_nodes,
                                      num_nodes)
        agg = lambda z, ss, sd, _: attention_agg_op(
            z, ss, sd, blocks, num_rows=num_nodes)
        h = features
        last = len(params.layers) - 1
        for i, lp in enumerate(params.layers):
            h = self.layer(lp, h, None, agg, i, i == last)
        return h

    # ------------------------------------------------------ no sampled path
    def apply_sampled(self, *args, **kwargs):
        raise _no_sampled_path("sampled apply")

    def make_loss_fn(self, *args, **kwargs):
        raise _no_sampled_path("sampled mini-batch loss")
