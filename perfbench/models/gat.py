"""GAT (Veličković et al., "Graph Attention Networks", ICLR 2018, arXiv
1710.10903), its inductive (PPI) configuration of §3.3, as the benchmark
runs it: a configuration whose ``arch`` is ``gat``.

Everything the benchmark knows of this model is here, found by the name
``perfbench/models/gat.py``:

  weights     ``layer_dims``, ``init_params``: per layer the projection
              ``w`` (d_in, K*F) and the attention vectors ``a_src``,
              ``a_dst`` (K, F) Glorot-uniform, a zero bias, from a key, on
              the device;
  program     ``build_program`` (the program's ``GAT``; it has no sampled
              loss, so none), ``to_program_params`` and
              ``from_program_params``;
  reference   ``full_logits``, ``rows_logits``: the plain forward in
              ``jax.numpy``, which imports nothing of the program;
              ``sampled_logits`` refuses (GAT runs full-graph only);
  FLOPs       ``train_flops``: model FLOPs of a window's training steps.

Per layer with ``K`` heads of width ``F`` and input ``h``:

    z      = h @ w                                 (N, K, F)
    e[u→v] = LeakyReLU_0.2(<a_src[k], z[u,k]> + <a_dst[k], z[v,k]>)
             for u in the in-neighbours of v, and v itself once
    α[u→v] = softmax over u of e[·→v], per v and head k
    o[v,k] = Σ_u α[u→v,k] · z[u,k]

Hidden layers: ELU(concat_k o + b), the middle layer with its input added
(an identity skip, DGL's residual where the widths agree); the last layer:
mean_k o + b.  Departures from the paper's PPI run are in the
configuration's ``assumed``: Flickr in place of PPI, so a softmax
cross-entropy over 7 classes; AdamW lr 1e-3, clip 5.

The heads are the published ones, :data:`HEADS`; ``layer_dims`` checks the
configuration against them, and the kernel files read them here (the
readers' context carries this module, not the configuration).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HEADS = (4, 4, 6)           # the paper's PPI heads per layer
LEAKY_SLOPE = 0.2


# ---------------------------------------------------------------- weights
def heads(config: dict) -> tuple[int, ...]:
    got = tuple(int(k) for k in config["heads"])
    if got != HEADS:
        raise ValueError(f"models/gat.py runs the published heads {HEADS}, "
                         f"the configuration gives {got}")
    if int(config["num_layers"]) != len(HEADS):
        raise ValueError(f"num_layers {config['num_layers']} != "
                         f"{len(HEADS)} layers of heads")
    return got


def head_widths(config: dict) -> tuple[int, ...]:
    """Per layer, one head's width: ``head_dim``, the classes last."""
    n = len(heads(config))
    return ((int(config["head_dim"]),) * (n - 1)
            + (int(config["num_classes"]),))


def layer_dims(config: dict) -> tuple[int, ...]:
    """(D, K1*F, K2*F, K3*C): the input width, then the width each layer's
    messages travel at."""
    return (int(config["feature_dim"]),) + tuple(
        k * f for k, f in zip(heads(config), head_widths(config)))


def init_params(config: dict, key):
    """Weights from ``key``, made on the device in one jitted call.
    Returns a list of per-layer dicts of float32 device arrays."""
    dims, hs, fs = layer_dims(config), heads(config), head_widths(config)

    @jax.jit
    def make(key):
        out = []
        for i, (k, f) in enumerate(zip(hs, fs)):
            kw, ks, kd = jax.random.split(jax.random.fold_in(key, i), 3)
            d_in, d_out = dims[i], k * f

            def u(key, shape, fan_in, fan_out):
                lim = float(np.sqrt(6.0 / (fan_in + fan_out)))
                return jax.random.uniform(key, shape, jnp.float32, -lim, lim)

            last = i == len(hs) - 1
            out.append({"w": u(kw, (d_in, d_out), d_in, d_out),
                        "a_src": u(ks, (k, f), f, 1),
                        "a_dst": u(kd, (k, f), f, 1),
                        "b": jnp.zeros((f if last else d_out,), jnp.float32)})
        return out

    return make(key)


# ---------------------------------------------------------------- program
def build_program(config: dict):
    """The program's model for ``config`` and its sampled loss, of which
    GAT has none: ``(model, None)``."""
    from repro.graph import GAT

    model = GAT(feature_dim=int(config["feature_dim"]),
                num_classes=int(config["num_classes"]),
                heads=heads(config), head_dim=int(config["head_dim"]))
    return model, None


def to_program_params(layers):
    from repro.graph.gat import GATLayer, GATParams

    return GATParams(layers=tuple(
        GATLayer(w=l["w"], a_src=l["a_src"], a_dst=l["a_dst"], b=l["b"])
        for l in layers))


def from_program_params(params) -> list[dict]:
    """Per-layer dicts of NumPy arrays from the program's params (or from
    any pytree of that shape, such as an optimizer moment)."""
    return [{"w": np.asarray(l.w), "a_src": np.asarray(l.a_src),
             "a_dst": np.asarray(l.a_dst), "b": np.asarray(l.b)}
            for l in params.layers]


# ---------------------------------------------------------------- reference
def sampled_logits(layers, x_t, x_1, x_2):
    raise ValueError("GAT runs on the full-graph path only: no sampled "
                     "forward")


def _block_max(edges, score, init):
    """Per destination of ``edges`` the maximum of ``score(src, dst)``
    ((B, K) per block) and ``init``: a scan over the edge blocks."""
    score = jax.checkpoint(score)

    def step(acc, block):
        s, d = block
        return acc.at[d].max(score(s, d), mode="drop"), None

    return jax.lax.scan(step, init, (edges.src, edges.dst))[0]


def _edge_sum(edges, msgs):
    """Per destination of ``edges`` the sum of ``msgs`` (one row per edge
    of the flattened blocks) over every edge at once: one segment sum, into
    which padding edges (destination ``num_out``) do not add.  On a TPU
    v5e a scan of per-block scatter-adds of GAT's messages, with or
    without the sorted claim of ``reference.Edges.sum``, put a layer 30%
    off this sum, which agrees with the program and with the CPU; so
    GAT's sums take the whole edge list, two heads at a time, and the
    layers are recomputed in the backward pass, to bound the memory that
    takes."""
    return jax.ops.segment_sum(msgs, edges.dst.reshape(-1),
                               num_segments=edges.num_out)


def _attend(lp, h, edges, rows=None, last=False):
    """One layer over every node (``rows`` None) or over the nodes
    ``rows``, whose in-edges ``edges`` carries (destination i is
    ``rows[i]``).  Two passes over the edges: the maximum score per
    destination (a scan over the blocks), then the exponentials' sums."""
    k = lp["a_src"].shape[0]
    z = (h @ lp["w"]).reshape(h.shape[0], k, -1)              # (N, K, F)
    zd = z if rows is None else z[rows]                       # destinations
    s_src = (z * lp["a_src"]).sum(-1)                          # (N, K)
    s_dst = (zd * lp["a_dst"]).sum(-1)                         # (n_out, K)
    s_self = s_src if rows is None else s_src[rows]
    e_self = jax.nn.leaky_relu(s_self + s_dst, LEAKY_SLOPE)
    n_out = edges.num_out

    def score(s, d):
        # padding edges (d == n_out) read the last row and are dropped
        d = jnp.minimum(d, n_out - 1)
        return jax.nn.leaky_relu(s_src[s] + s_dst[d], LEAKY_SLOPE)

    # the shift cancels in the ratio: no gradient through it
    m = jax.lax.stop_gradient(_block_max(edges, score, e_self))
    s, d = edges.src.reshape(-1), edges.dst.reshape(-1)
    # per edge and head exp(e - m[dst]); padding edges take no weight
    w = jnp.exp(jnp.where((d < n_out)[:, None],
                          score(s, d) - m[jnp.minimum(d, n_out - 1)],
                          -jnp.inf))                            # (E, K)
    p_self = jnp.exp(e_self - m)
    den = p_self + _edge_sum(edges, w)
    # two heads at a time: each sum's per-edge rows at most 512 wide
    num = jnp.concatenate([
        _edge_sum(edges, (w[:, j:j + 2, None] * z[s, j:j + 2])
                  .reshape(s.shape[0], -1)).reshape(n_out, -1, z.shape[-1])
        for j in range(0, k, 2)], axis=1)                      # (n_out, K, F)
    o = (num + p_self[..., None] * zd) / den[..., None]       # (n_out, K, F)
    if last:
        return o.mean(axis=1) + lp["b"]
    return o.reshape(n_out, -1) + lp["b"]


def full_logits(layers, feats, edges):
    """Every node's logits.  ``edges`` (``reference.Edges``) carries the
    whole graph's in-edges."""
    h = feats
    last = len(layers) - 1
    for i, lp in enumerate(layers):
        out = jax.checkpoint(
            lambda lp, h, i=i: _attend(lp, h, edges, last=i == last))(lp, h)
        if i < last:
            out = jax.nn.elu(out + h if i > 0 else out)
        h = out
    return h


def rows_logits(layers, feats, edges, rows, last_edges):
    """Logits of the nodes ``rows``: every layer but the last over the
    whole graph (``edges``), the last over ``rows`` alone, whose in-edges
    ``last_edges`` carries (destination i is ``rows[i]``)."""
    h = feats
    for i, lp in enumerate(layers[:-1]):
        out = _attend(lp, h, edges)
        h = jax.nn.elu(out + h if i > 0 else out)
    return _attend(layers[-1], h, last_edges, rows=rows, last=True)


# ---------------------------------------------------------------- FLOPs
def fullgraph_step_flops(dims, owned: int, edges: int) -> float:
    """Forward FLOPs of one partition's full-graph step: per layer the
    projection of its owned rows, their two attention scores per head,
    and the attention-weighted sum over its real edges and each row's own
    term (a multiply and an add per element)."""
    total = 0.0
    for d_in, w in zip(dims[:-1], dims[1:]):
        total += (2.0 * owned * d_in * w + 2.0 * 2.0 * owned * w
                  + 2.0 * (float(edges) + owned) * w)
    return total


def train_flops(ctx) -> float:
    """Model FLOPs of the training steps in a window (all partitions, all
    chips): each step's forward and a backward at twice the forward.  Real
    owned nodes and real edges only; the evaluation forward does not
    count.  ``ctx`` is a ``readers.Context``."""
    if ctx.kind != "fullgraph":
        return 0.0
    per_step = 3.0 * sum(
        fullgraph_step_flops(ctx.dims, ctx.owned[p], ctx.edges[p])
        for p in range(len(ctx.owned)))
    return per_step * sum(e.steps for e in ctx.epochs)
