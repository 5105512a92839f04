"""Plain reference of what a cell's timed steps compute: the model's
forward (its module's ``sampled_logits``, ``full_logits``,
``rows_logits``), softmax cross-entropy, the mean of the partitions'
gradients, global-norm clipping and AdamW, written in straightforward
``jax.numpy``.

Imports nothing of the program and takes nothing it made: the weights come
from the model module's own initializer, the features and labels from the
benchmark's generator, and the inputs of each step are node ids (the
sampled batches) or the partition of each node (full-graph steps).

``dtype=float32`` runs every matmul at ``highest`` precision; any other
dtype (``bfloat16``: the control) casts every array to it and computes
there, matmuls at the chip's native precision.

Full-graph steps aggregate over the whole graph; the partitioned
program's exchange of halo rows each layer makes its owned rows equal to
this.  Partition p's loss is the mean over its own training nodes, and the
step's gradient is the mean of the partitions' gradients.  Every
aggregation runs over the edges in fixed-size blocks (:class:`Edges`), so
the reference's memory does not grow with the edge count.

After each epoch the validation forward is the same whole-graph forward,
its last layer taken over the validation nodes alone.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["EDGE_BLOCK", "Edges", "Reference", "edge_blocks"]

# edges per aggregation block: 512 MiB of gathered float32 rows at width 256
EDGE_BLOCK = 1 << 19


def edge_blocks(src: np.ndarray, dst: np.ndarray, num_out: int,
                block: int = EDGE_BLOCK) -> tuple[np.ndarray, np.ndarray]:
    """Edges ``src[e] -> dst[e]`` (destinations sorted) as ``(blocks, B)``
    int32 arrays, ``B = min(block, E)``; the last block is padded with
    edges into ``num_out``, a destination the sums drop."""
    e = len(src)
    b = max(1, min(int(block), e))
    n = -(-e // b) * b
    s = np.zeros(n, np.int32)
    d = np.full(n, num_out, np.int32)
    s[:e], d[:e] = src, dst
    return s.reshape(-1, b), d.reshape(-1, b)


@dataclass(frozen=True)
class Edges:
    """In-edges of ``num_out`` destinations in blocks (:func:`edge_blocks`)
    and each destination's inverse degree, inside a traced function."""

    src: jax.Array                # (blocks, B)
    dst: jax.Array                # (blocks, B), sorted; num_out = padding
    num_out: int
    inv_deg: jax.Array            # (num_out,)

    def sum(self, message):
        """Per destination the sum of ``message(src, dst)`` ((B, W) rows
        of one block's edges) over its in-edges: a scan over the blocks
        that scatter-adds each block's messages into the running sum (as
        ``segment_sum`` does into zeros), the block's messages recomputed
        in the backward pass (``jax.checkpoint``) rather than kept."""
        msg = jax.checkpoint(message)
        out = jax.eval_shape(msg, self.src[0], self.dst[0])

        def add(acc, block):
            s, d = block
            return acc.at[d].add(msg(s, d), indices_are_sorted=True,
                                 mode="drop"), None

        acc = jnp.zeros((self.num_out,) + out.shape[1:], out.dtype)
        return jax.lax.scan(add, acc, (self.src, self.dst))[0]


def masked_ce(logits, labels, mask):
    """Mean softmax cross-entropy over the rows where ``mask`` is set."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    w = mask.astype(nll.dtype)
    return (nll * w).sum() / jnp.maximum(w.sum(), 1)


class Reference:
    """Steps of one cell from given weights, in ``dtype``, with the
    forward of ``model`` (the cell's model module)."""

    def __init__(self, config: dict, model, graph, parts: np.ndarray,
                 dtype=jnp.float32, edge_block: int = EDGE_BLOCK):
        self.cfg, self.model, self.graph = config, model, graph
        self.edge_block = int(edge_block)
        self.parts = np.asarray(parts)
        self.num_parts = int(self.parts.max()) + 1
        self.dtype = jnp.dtype(dtype)
        self.lr = float(config["lr"])
        self.b1, self.b2 = float(config["adam_b1"]), float(config["adam_b2"])
        self.eps = float(config["adam_eps"])
        self.clip = float(config["grad_clip"])
        self._full = None
        self._steps = {}

    def _precision(self):
        if self.dtype == jnp.float32:
            return jax.default_matmul_precision("highest")
        return contextlib.nullcontext()

    def cast(self, tree):
        """Floating leaves to the reference's dtype; ids and masks as-is."""
        def one(x):
            x = np.asarray(x) if not isinstance(x, jax.Array) else x
            if jnp.issubdtype(x.dtype, jnp.floating):
                return jnp.asarray(x, self.dtype)
            return jnp.asarray(x)
        return jax.tree.map(one, tree)

    # -- one optimizer step ---------------------------------------------
    def _update(self, layers, opt, grads):
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree.leaves(grads))
        scale = jnp.minimum(1.0, self.clip / (jnp.sqrt(sq) + 1e-9))
        grads = jax.tree.map(lambda g: (g * scale).astype(self.dtype), grads)
        t = opt["t"] + 1
        mu = jax.tree.map(lambda m, g: self.b1 * m + (1 - self.b1) * g,
                          opt["mu"], grads)
        nu = jax.tree.map(lambda v, g: self.b2 * v + (1 - self.b2) * g * g,
                          opt["nu"], grads)
        tf = t.astype(jnp.float32)
        c1 = (1.0 / (1.0 - self.b1 ** tf)).astype(self.dtype)
        c2 = (1.0 / (1.0 - self.b2 ** tf)).astype(self.dtype)
        layers = jax.tree.map(
            lambda p, m, v: p - self.lr * (m * c1) / (jnp.sqrt(v * c2)
                                                      + self.eps),
            layers, mu, nu)
        return layers, {"t": t, "mu": mu, "nu": nu}

    def init_opt(self, layers):
        z = jax.tree.map(jnp.zeros_like, layers)
        return {"t": jnp.zeros((), jnp.int32), "mu": z,
                "nu": jax.tree.map(jnp.zeros_like, layers)}

    # -- sampled steps ---------------------------------------------------
    def sampled_inputs(self, ids: list, num_parts: int) -> list[dict]:
        """Recorded ids in (iteration, partition) order -> per-iteration
        stacked (P, ...) inputs gathered from the generator's features."""
        f = self.graph.features
        lab = self.graph.labels
        out = []
        for i in range(0, len(ids), num_parts):
            rows = ids[i:i + num_parts]
            b, f1 = rows[0]["nbrs1"].shape
            f2 = rows[0]["nbrs2"].shape[1]
            out.append({
                "x_t": np.stack([f[r["targets"]] for r in rows]),
                "x_1": np.stack([f[r["nbrs1"].reshape(-1)].reshape(b, f1, -1)
                                 for r in rows]),
                "x_2": np.stack([f[r["nbrs2"].reshape(-1)].reshape(
                    b, f1, f2, -1) for r in rows]),
                "labels": np.stack([np.maximum(lab[r["targets"]], 0)
                                    for r in rows]),
                "mask": np.stack([r["mask"] * (lab[r["targets"]] >= 0)
                                  for r in rows])})
        return out

    def _sampled_step_fn(self):
        def losses_of(layers, x):
            per = jax.vmap(lambda xt, x1, x2, y, m: masked_ce(
                self.model.sampled_logits(layers, xt, x1, x2), y, m))(
                x["x_t"], x["x_1"], x["x_2"], x["labels"], x["mask"])
            return per.sum() / per.shape[0], per

        def step(layers, opt, x):
            (_, per), grads = jax.value_and_grad(losses_of, has_aux=True)(
                layers, x)
            layers, opt = self._update(layers, opt, grads)
            return layers, opt, per

        return self._steps.setdefault("sampled", jax.jit(step))

    # -- full-graph steps ------------------------------------------------
    def _full_inputs(self):
        if self._full is None:
            g = self.graph
            n = g.num_nodes
            dst = np.repeat(np.arange(n), np.diff(g.indptr))
            deg = np.maximum(np.diff(g.indptr), 1).astype(np.float32)
            train = np.zeros(n, bool)
            train[g.train_idx] = True
            train &= g.labels >= 0
            src, dst = edge_blocks(g.indices, dst, n, self.edge_block)
            self._full = {"src": src, "dst": dst,
                          "inv_deg": 1.0 / deg,
                          "feats": g.features,
                          "labels": np.maximum(g.labels, 0),
                          "masks": np.stack([train & (self.parts == p)
                                             for p in range(self.num_parts)])}
        return self._full

    def _full_step_fn(self):
        def losses_of(layers, x):
            edges = Edges(x["src"], x["dst"], x["feats"].shape[0],
                          x["inv_deg"])
            logits = self.model.full_logits(layers, x["feats"], edges)
            per = jax.vmap(lambda m: masked_ce(logits, x["labels"], m))(
                x["masks"])
            return per.sum() / per.shape[0], per

        def step(layers, opt, x):
            (_, per), grads = jax.value_and_grad(losses_of, has_aux=True)(
                layers, x)
            layers, opt = self._update(layers, opt, grads)
            return layers, opt, per

        return self._steps.setdefault("full", jax.jit(step))

    # -- the validation forward -------------------------------------------
    def _eval_inputs(self, rows: np.ndarray) -> dict:
        x = dict(self._full_inputs())
        g = self.graph
        rows = np.asarray(rows, np.int64)
        deg = np.diff(g.indptr)[rows]
        last_src = np.concatenate(
            [g.indices[g.indptr[r]:g.indptr[r + 1]] for r in rows]
            ).astype(np.int32) if len(rows) else np.zeros(0, np.int32)
        last_pos = np.repeat(np.arange(len(rows)), deg).astype(np.int32)
        x["last_src"], x["last_dst"] = edge_blocks(
            last_src, last_pos, len(rows), self.edge_block)
        x["rows"] = rows.astype(np.int32)
        x.pop("labels"), x.pop("masks")
        return x

    def _eval_fn(self):
        def logits(layers, x):
            n, rows = x["feats"].shape[0], x["rows"]
            edges = Edges(x["src"], x["dst"], n, x["inv_deg"])
            last = Edges(x["last_src"], x["last_dst"], rows.shape[0],
                         x["inv_deg"][rows])
            return self.model.rows_logits(layers, x["feats"], edges, rows,
                                          last)

        return self._steps.setdefault("eval", jax.jit(logits))

    def eval_logits(self, layers, rows: np.ndarray) -> np.ndarray:
        """``(len(rows), C)`` float32 logits of the nodes ``rows``."""
        key = ("eval_inputs", len(rows))
        if key not in self._steps:
            self._steps[key] = self.cast(self._eval_inputs(rows))
        return np.asarray(self._eval_fn()(layers, self._steps[key]),
                          np.float32)

    # -- the checked run -------------------------------------------------
    def run(self, layers0, epochs: list, eval_rows=None) -> dict:
        """Follow the program's first epochs from ``layers0``.  ``epochs``
        holds per epoch the recorded sampled ids (a list) or, for a
        full-graph epoch, its number of steps (an int).  Returns the
        per-epoch losses ``(steps, P)``, the first moment after the first
        epoch and the weights after the last, all float32 NumPy; with
        ``eval_rows`` (node ids) also, after each epoch, their logits
        (``val_logits``) and the class each puts first (``val_preds``)."""
        layers = self.cast(layers0)
        opt = self.init_opt(layers)
        losses, mu1, val_logits = [], None, []
        with self._precision():
            for e, spec in enumerate(epochs):
                per_epoch = []
                if isinstance(spec, int):
                    step = self._full_step_fn()
                    x = self.cast(self._full_inputs())
                    for _ in range(spec):
                        layers, opt, per = step(layers, opt, x)
                        per_epoch.append(np.asarray(per, np.float32))
                else:
                    step = self._sampled_step_fn()
                    for x in self.sampled_inputs(spec, self.num_parts):
                        x = self.cast(x)
                        layers, opt, per = step(layers, opt, x)
                        per_epoch.append(np.asarray(per, np.float32))
                losses.append(np.stack(per_epoch))
                if e == 0:
                    mu1 = jax.tree.map(lambda m: np.asarray(m, np.float32),
                                       opt["mu"])
                if eval_rows is not None:
                    val_logits.append(self.eval_logits(layers, eval_rows))
        return {"losses": losses, "mu1": mu1,
                "params": jax.tree.map(lambda p: np.asarray(p, np.float32),
                                       layers),
                "val_logits": val_logits,
                "val_preds": [np.argmax(v, axis=-1) for v in val_logits]}
