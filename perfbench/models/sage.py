"""GraphSAGE with the mean aggregator (Hamilton et al. 2017, Eq. 1-2), as
the benchmark runs it: a configuration whose ``arch`` is ``sage``.

Everything the benchmark knows of this model is here, found by the name
``perfbench/models/sage.py``:

  weights     ``layer_dims``, ``init_params``: per layer (w_self, w_neigh)
              Glorot-uniform and a zero bias, from a key, on the device;
  program     ``build_program`` (the program's ``GraphSAGE`` and its
              cross-entropy loss), ``to_program_params`` and
              ``from_program_params``;
  reference   ``sampled_logits``, ``full_logits``, ``rows_logits``: the
              plain forward in ``jax.numpy``, which imports nothing of the
              program;
  FLOPs       ``train_flops``: model FLOPs of a window's training steps.

The program is imported inside the functions that build or convert its
objects, so the reference side of this file stays free of it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------- weights
def layer_dims(config: dict) -> tuple[int, ...]:
    n = int(config["num_layers"])
    return ((int(config["feature_dim"]),) + (int(config["hidden_dim"]),)
            * (n - 1) + (int(config["num_classes"]),))


def init_params(config: dict, key):
    """Weights from ``key``, made on the device in one jitted call: per
    layer (w_self, w_neigh) Glorot-uniform and a zero bias.  Returns a
    list of per-layer dicts of float32 device arrays."""
    dims = layer_dims(config)

    @jax.jit
    def make(key):
        out = []
        for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
            k1, k2 = jax.random.split(jax.random.fold_in(key, i))
            lim = float(np.sqrt(6.0 / (d_in + d_out)))
            u = lambda k: jax.random.uniform(k, (d_in, d_out), jnp.float32,
                                             -lim, lim)
            out.append({"w_self": u(k1), "w_neigh": u(k2),
                        "b": jnp.zeros((d_out,), jnp.float32)})
        return out

    return make(key)


# ---------------------------------------------------------------- program
def build_program(config: dict):
    """The program's model for ``config`` and the loss its engine trains
    with: ``(model, loss_fn)``."""
    from repro.graph import GraphSAGE

    model = GraphSAGE(feature_dim=int(config["feature_dim"]),
                      hidden_dim=int(config["hidden_dim"]),
                      num_classes=int(config["num_classes"]),
                      num_layers=int(config["num_layers"]))
    return model, model.make_loss_fn("ce")


def to_program_params(layers):
    from repro.graph.sage import SAGELayer, SAGEParams

    return SAGEParams(layers=tuple(
        SAGELayer(w_self=l["w_self"], w_neigh=l["w_neigh"], b=l["b"])
        for l in layers))


def from_program_params(params) -> list[dict]:
    """Per-layer dicts of NumPy arrays from the program's params (or from
    any pytree of that shape, such as an optimizer moment)."""
    return [{"w_self": np.asarray(l.w_self), "w_neigh": np.asarray(l.w_neigh),
             "b": np.asarray(l.b)} for l in params.layers]


# ---------------------------------------------------------------- reference
def _layer(lp, h_self, h_neigh, last: bool):
    out = h_self @ lp["w_self"] + h_neigh @ lp["w_neigh"] + lp["b"]
    return out if last else jax.nn.relu(out)


def sampled_logits(layers, x_t, x_1, x_2):
    """Targets (B, D), their sampled neighbours (B, F1, D) and those
    neighbours' samples (B, F1, F2, D) -> (B, C)."""
    l1, l2 = layers
    h_t = _layer(l1, x_t, x_1.mean(axis=1), last=False)
    h_1 = _layer(l1, x_1, x_2.mean(axis=2), last=False)
    return _layer(l2, h_t, h_1.mean(axis=1), last=True)


def _mean(edges, h):
    """Per destination of ``edges`` the mean of ``h`` over its
    in-neighbours."""
    return edges.sum(lambda src, dst: h[src]) * edges.inv_deg[:, None]


def full_logits(layers, feats, edges):
    """Every node's logits.  ``edges`` (``reference.Edges``) carries the
    whole graph's in-edges and each node's inverse degree."""
    h = feats
    for i, lp in enumerate(layers):
        h = _layer(lp, h, _mean(edges, h), last=i == len(layers) - 1)
    return h


def rows_logits(layers, feats, edges, rows, last_edges):
    """Logits of the nodes ``rows``: every layer but the last over the
    whole graph (``edges``), the last over ``rows`` alone, whose in-edges
    ``last_edges`` carries (destination i is ``rows[i]``)."""
    h = feats
    for lp in layers[:-1]:
        h = _layer(lp, h, _mean(edges, h), last=False)
    return _layer(layers[-1], h[rows], _mean(last_edges, h), last=True)


# ---------------------------------------------------------------- FLOPs
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


def train_flops(ctx) -> float:
    """Model FLOPs of the training steps in a window (all partitions, all
    chips): each step's forward and a backward at twice the forward.
    Real seeds, real owned nodes and real edges only; the evaluation
    forward does not count.  ``ctx`` is a ``readers.Context``."""
    if ctx.kind == "sampled":
        per_seed = 3.0 * sampled_seed_flops(ctx.dims, ctx.fanouts)
        return per_seed * sum(e.nodes for e in ctx.epochs)
    per_step = 3.0 * sum(
        fullgraph_step_flops(ctx.dims, ctx.owned[p], ctx.edges[p])
        for p in range(len(ctx.owned)))
    return per_step * sum(e.steps for e in ctx.epochs)
