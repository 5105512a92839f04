"""Sequential reference driver — the parity oracle for the SPMD engine.

Executes the EXACT math of :class:`repro.engine.spmd.SPMDEngine` as legible
Python loops over partitions: per-partition gradients in a loop, the
all-reduce as a deterministic stack-and-sum, the halo exchange as explicit
gather / transpose / scatter.  ``tests/test_engine_parity.py`` asserts the
fused engine reproduces this path's losses and micro-F1 bit-for-bit in
float64 — the self-verification the refactor ships with (DESIGN.md §3).

Aggregation always uses the jnp segment-op reference (kernels/ref.py math):
the Pallas kernel is validated against the same reference separately in
tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.gp.trainer import (GPHyperParams, GRAD_COMPRESS_MODES,
                               make_bucketed_reduce_stacked,
                               make_personalize_partition_step,
                               make_topk_reduce_stacked)
from ..graph.distributed import (HALO_COMPRESS_MODES, PartitionedGraph,
                                 dequantize_rows, halo_refresh_plan,
                                 make_ref_mean_agg, make_ref_split_agg,
                                 quantize_rows, wire_row_bytes)
from ..train.metrics import f1_scores_jnp
from ..train.optim import apply_updates

__all__ = ["SequentialReference"]


class SequentialReference:
    """Same public surface as SPMDEngine (phase0_epoch / phase1_epoch /
    evaluate), Python-loop execution."""

    mode = "sequential"

    def __init__(self, model, loss_fn, optimizer, pg: PartitionedGraph,
                 hp: GPHyperParams = GPHyperParams(), config=None):
        if model.aggregation != "mean":
            raise ValueError(
                f"engine mode 'sequential' writes GraphSAGE's mean layer "
                f"out op for op; {type(model).__name__} runs on the stacked "
                f"or spmd engine")
        f = config.dtype if config is not None else jnp.float32
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.num_parts = pg.num_parts
        self.num_classes = model.num_classes
        self.max_nodes = pg.max_nodes
        self.own_cap = pg.own_cap
        self.overlap = bool(getattr(config, "overlap_halo", False))
        self._fg_loss_kind = getattr(config, "fg_loss", "ce")
        # compressed communication (DESIGN.md §11), mirrored from the engine
        self.halo_compress = str(getattr(config, "halo_compress", "none"))
        self.grad_compress = str(getattr(config, "grad_compress", "none"))
        self._grad_topk_frac = float(getattr(config, "grad_topk_frac", 0.01))
        self._grad_bucket_kb = int(getattr(config, "grad_bucket_kb", 512))
        if self.halo_compress not in HALO_COMPRESS_MODES:
            raise ValueError(f"unknown halo_compress {self.halo_compress!r} "
                             f"(expected one of {HALO_COMPRESS_MODES})")
        if self.grad_compress not in GRAD_COMPRESS_MODES:
            raise ValueError(f"unknown grad_compress {self.grad_compress!r} "
                             f"(expected one of {GRAD_COMPRESS_MODES})")
        if self.halo_compress != "none" and self.overlap:
            raise ValueError(
                "halo_compress quantizes the gathered send buffer on the "
                "combined-edge eval forward; the overlap forward has no "
                "compressed spelling — pick one")
        if bool(getattr(config, "feat_store", False)):
            raise ValueError(
                "SequentialReference IS the all-resident oracle the "
                "feat-store engine is locked against; build it without "
                "feat_store (a feat-store DeviceEpochSampler is still "
                "accepted — its gather is bitwise the resident one)")
        self.features = jnp.asarray(pg.features, f)        # (P, maxN, D)
        self.send_idx = jnp.asarray(pg.send_idx)
        self.send_mask = jnp.asarray(pg.send_mask, f)
        self.recv_pos = jnp.asarray(pg.recv_pos)
        self.labels = jnp.asarray(pg.labels)
        self.masks = {
            "train": np.asarray(pg.train_mask),
            "val": np.asarray(pg.val_mask),
            "test": np.asarray(pg.test_mask),
        }
        # per-partition edge views for whichever forward this config runs:
        # either the combined-edge reference aggregation, or (overlap) the
        # destination-disjoint CSR shards + static degree + interior counts
        self.n_int = np.asarray(pg.n_int)
        if self.overlap:
            self._agg_int, self._agg_bnd = make_ref_split_agg(pg.own_cap)
            self._split_shards = [
                {"int_src": jnp.asarray(pg.int_src[p]),
                 "int_dst": jnp.asarray(pg.int_dst[p]),
                 "bnd_src": jnp.asarray(pg.bnd_src[p]),
                 "bnd_dst": jnp.asarray(pg.bnd_dst[p]),
                 "deg": jnp.asarray(pg.deg[p], f)}
                for p in range(pg.num_parts)
            ]
        else:
            self._agg = make_ref_mean_agg(pg.max_nodes)
            self._edge_shards = [
                {"edge_src": jnp.asarray(pg.edge_src[p]),
                 "edge_dst": jnp.asarray(pg.edge_dst[p]),
                 "edge_mask": jnp.asarray(pg.edge_mask[p], f)}
                for p in range(pg.num_parts)
            ]
        self.halo_cache = bool(getattr(config, "halo_cache", False))
        self.last_halo_exchange_bytes = 0
        if self.halo_cache:
            if self.overlap:
                raise ValueError(
                    "halo_cache and overlap_halo are alternative exchange "
                    "optimisations: the cache removes the very exchange the "
                    "overlap would hide — pick one")
            self.halo_refresh_every = int(getattr(config,
                                                  "halo_refresh_every", 1))
            self.halo_cv = bool(getattr(config, "halo_cv", False))
            self.max_send = pg.send_idx.shape[-1]
            self._halo_slot_counts = np.asarray(pg.send_mask).sum(axis=(0, 1))
            self._halo_byte_per_slot = wire_row_bytes(
                pg.features.shape[-1], self.halo_compress,
                pg.features.dtype.itemsize)
            # per-partition recv buffers, one per layer — the legible
            # rendering of the engine's stacked (P, P, maxS, D) cache state
            Pn = pg.num_parts
            self._halo_state = {
                f"h{i}": [jnp.zeros((Pn, self.max_send, d), f)
                          for _ in range(Pn)]
                for i, d in enumerate(model.layer_input_dims)}
            self._halo_age = 0
        self._halo_dtype = f
        self._halo_rows_total = int(pg.n_halo.sum())
        self._halo_row_width = pg.features.shape[-1]
        self._halo_itemsize = pg.features.dtype.itemsize
        if self.halo_compress != "none":
            # per-partition send-side quantization error, one (P, maxS, d)
            # buffer per sender per layer — the legible rendering of the
            # engine's stacked build_stacked_halo_residual state
            Pn = pg.num_parts
            ms = pg.send_idx.shape[-1]
            self._halo_residual = {
                f"r{i}": [jnp.zeros((Pn, ms, d), f) for _ in range(Pn)]
                for i, d in enumerate(model.layer_input_dims)}
        self._grad_res = None   # lazy (P, N) top-k error-feedback state
        self._grad_step = jax.jit(jax.value_and_grad(loss_fn))
        self._pstep1 = jax.jit(make_personalize_partition_step(
            loss_fn, optimizer, hp))
        self._device_sampler = None
        self.last_eval_seconds = 0.0   # wall time of the latest standalone
                                       # _eval (first call includes jit)

        # the all-reduce + optimizer update runs as ONE jitted function:
        # AdamW keeps float32 moments, and XLA's fused rounding of that
        # arithmetic differs from eager op-by-op dispatch at the last ulp —
        # jitting at this granularity is what makes the engine's in-scan
        # update bit-for-bit reproducible here (see test_engine_parity)
        P = pg.num_parts

        @jax.jit
        def _apply_avg(params, opt_state, grads_stacked):
            grads = jax.tree.map(lambda g: jnp.sum(g, axis=0) / P,
                                 grads_stacked)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            return apply_updates(params, updates), opt_state

        self._apply_avg = _apply_avg

        # compressed gradient syncs jit at the SAME granularity (reduce +
        # update in one function) for the fused-rounding parity above
        if self.grad_compress == "bucketed":
            red_b = make_bucketed_reduce_stacked(P, self._grad_bucket_kb * 1024)

            @jax.jit
            def _apply_bucketed(params, opt_state, grads_stacked):
                grads = red_b(grads_stacked)
                updates, opt_state = optimizer.update(grads, opt_state, params)
                return apply_updates(params, updates), opt_state

            self._apply_grads = _apply_bucketed
        else:
            self._apply_grads = _apply_avg
        if self.grad_compress == "topk":
            red_t = make_topk_reduce_stacked(P, self._grad_topk_frac)

            @jax.jit
            def _apply_topk(params, opt_state, grads_stacked, res):
                grads, res = red_t(grads_stacked, res)
                updates, opt_state = optimizer.update(grads, opt_state, params)
                return apply_updates(params, updates), opt_state, res

            self._apply_topk = _apply_topk

    # --------------------------------------------------------- forward pass
    def _exchange(self, hs: list) -> list:
        """Explicit halo exchange: recv[q][p] = sent[p][q] (the all_to_all
        transpose), scattered into each partition's halo slots."""
        P = self.num_parts
        sent = [hs[p][self.send_idx[p]] * self.send_mask[p][..., None]
                for p in range(P)]                     # each (P, maxS, D)
        out = []
        for q in range(P):
            recv = jnp.stack([sent[p][q] for p in range(P)])
            flat_pos = self.recv_pos[q].reshape(-1)
            flat_val = recv.reshape(-1, hs[q].shape[-1])
            out.append(hs[q].at[flat_pos].set(flat_val.astype(hs[q].dtype)))
        return out

    def _exchange_comp(self, hs: list, rkey: str) -> list:
        """Error-compensated quantized rendering of :meth:`_exchange` — the
        legible mirror of ``_ef_quantized_exchange``: per sender, fold last
        round's residual into the gathered send buffer, quantize, update
        ``self._halo_residual[rkey]``, then transpose and scatter the
        DEQUANTIZED rows.  Sender-side dequantization is bitwise the
        receiver's (elementwise, deterministic), so dequantizing before the
        transpose models the wire exactly."""
        P = self.num_parts
        mode = self.halo_compress
        res = self._halo_residual[rkey]
        deqs = []
        for p in range(P):
            m3 = self.send_mask[p][..., None]
            sent = hs[p][self.send_idx[p]] * m3
            sent_ef = (sent + res[p].astype(sent.dtype)) * m3
            payload, scale = quantize_rows(sent_ef, mode)
            deq = dequantize_rows(payload, scale, mode, sent.dtype)
            res[p] = ((sent_ef - deq) * m3).astype(res[p].dtype)
            deqs.append(deq)
        out = []
        for q in range(P):
            recv = jnp.stack([deqs[p][q] for p in range(P)])
            flat_pos = self.recv_pos[q].reshape(-1)
            flat_val = recv.reshape(-1, hs[q].shape[-1])
            out.append(hs[q].at[flat_pos].set(flat_val.astype(hs[q].dtype)))
        return out

    def _exchange_cached(self, hs: list, key: str, lo: int, hi: int) -> list:
        """Historical-cache variant of :meth:`_exchange`: land each
        partition's CACHED recv buffers into the halo slots, then exchange
        only send slots ``[lo, hi)`` live and overwrite both the halo rows
        and the cache with the refreshed values.  The full-refresh case
        skips the cache landing entirely, so its op sequence is exactly
        :meth:`_exchange` (the staleness-0 bitwise contract).  Mutates
        ``self._halo_state[key]``."""
        P = self.num_parts
        full = lo == 0 and hi == self.max_send
        cache = self._halo_state[key]
        if hi > lo:
            # gather BEFORE any cache landing (send_idx only ever points at
            # owned rows, and the engine's cached forward uses this order)
            sent = [hs[p][self.send_idx[p][:, lo:hi]]
                    * self.send_mask[p][:, lo:hi][..., None]
                    for p in range(P)]
            if self.halo_compress != "none":
                # quantize the refresh payload with error feedback on the
                # matching residual slot slice; downstream the cache stores
                # the dequantized rows, exactly as the engine's cached
                # forward does
                mode = self.halo_compress
                res = self._halo_residual["r" + key[1:]]
                for p in range(P):
                    m3 = self.send_mask[p][:, lo:hi][..., None]
                    r_sl = res[p][:, lo:hi]
                    sent_ef = (sent[p] + r_sl.astype(sent[p].dtype)) * m3
                    payload, scale = quantize_rows(sent_ef, mode)
                    deq = dequantize_rows(payload, scale, mode,
                                          sent[p].dtype)
                    res[p] = res[p].at[:, lo:hi].set(
                        ((sent_ef - deq) * m3).astype(res[p].dtype))
                    sent[p] = deq
        out = []
        for q in range(P):
            h = hs[q]
            if not full:
                h = h.at[self.recv_pos[q].reshape(-1)].set(
                    cache[q].reshape(-1, h.shape[-1]).astype(h.dtype))
            if hi > lo:
                recv = jnp.stack([sent[p][q] for p in range(P)])
                h = h.at[self.recv_pos[q][:, lo:hi].reshape(-1)].set(
                    recv.reshape(-1, h.shape[-1]).astype(h.dtype))
                cache[q] = cache[q].at[:, lo:hi].set(
                    recv.astype(cache[q].dtype))
            out.append(h)
        return out

    def _full_forward_cached(self, params_list: list) -> list:
        """The cached eval forward: same layer schedule as
        :meth:`_full_forward`, halo rows served from the historical cache
        with the refresh slot range chosen by :func:`halo_refresh_plan`.
        Ages the cache once per call and records the refreshed payload in
        ``last_halo_exchange_bytes``."""
        P = self.num_parts
        lo, hi = halo_refresh_plan(self._halo_age, self.halo_refresh_every,
                                   self.halo_cv, self.max_send)
        hs = [self.features[p] for p in range(P)]
        num_layers = len(params_list[0].layers)
        for i in range(num_layers):
            hs = self._exchange_cached(hs, f"h{i}", lo, hi)
            nxt = []
            for p in range(P):
                lp = params_list[p].layers[i]
                agg = self._agg(hs[p], self._edge_shards[p])
                out = hs[p] @ lp.w_self + agg @ lp.w_neigh + lp.b
                nxt.append(jax.nn.relu(out) if i < num_layers - 1 else out)
            hs = nxt
        real = int(self._halo_slot_counts[lo:hi].sum())
        self.last_halo_exchange_bytes = (num_layers * real
                                         * self._halo_byte_per_slot)
        self._halo_age += 1
        return hs

    def _full_forward_comp(self, params_list: list) -> list:
        """Quantized-exchange eval forward: the plain layer schedule with
        :meth:`_exchange_comp` carrying the per-layer residual.  Records the
        compressed wire payload in ``last_halo_exchange_bytes``."""
        P = self.num_parts
        hs = [self.features[p] for p in range(P)]
        num_layers = len(params_list[0].layers)
        for i in range(num_layers):
            hs = self._exchange_comp(hs, f"r{i}")
            nxt = []
            for p in range(P):
                lp = params_list[p].layers[i]
                agg = self._agg(hs[p], self._edge_shards[p])
                out = hs[p] @ lp.w_self + agg @ lp.w_neigh + lp.b
                nxt.append(jax.nn.relu(out) if i < num_layers - 1 else out)
            hs = nxt
        self.last_halo_exchange_bytes = (num_layers
                                         * self.halo_wire_bytes_per_layer)
        return hs

    def _full_forward(self, params_list: list) -> list:
        """Layer-synchronous n-layer GraphSAGE over all partitions — the same
        schedule the per-shard fwd runs, unrolled in Python."""
        if self.overlap:
            return self._full_forward_overlap(params_list)
        if self.halo_cache:
            return self._full_forward_cached(params_list)
        if self.halo_compress != "none":
            return self._full_forward_comp(params_list)
        return self._full_forward_plain(params_list)

    def _full_forward_plain(self, params_list: list) -> list:
        P = self.num_parts
        hs = [self.features[p] for p in range(P)]
        num_layers = len(params_list[0].layers)
        for i in range(num_layers):
            hs = self._exchange(hs)
            nxt = []
            for p in range(P):
                lp = params_list[p].layers[i]
                agg = self._agg(hs[p], self._edge_shards[p])
                out = hs[p] @ lp.w_self + agg @ lp.w_neigh + lp.b
                nxt.append(jax.nn.relu(out) if i < num_layers - 1 else out)
            hs = nxt
        return hs

    def _split_layer(self, hs: list, layers: list, activate: bool) -> list:
        """One boundary/interior split layer, unrolled in Python — the
        legible rendering of make_overlap_forward's schedule: interior
        aggregation and the self-term run on the pre-exchange embeddings
        (the work that hides the exchange), boundary aggregation on the
        post-exchange ones, and a bitwise-safe per-row select joins them."""
        P, oc = self.num_parts, self.own_cap
        agg_i = [self._agg_int(hs[p], self._split_shards[p]) for p in range(P)]
        self_t = [hs[p][:oc] @ layers[p].w_self for p in range(P)]
        hs = self._exchange(hs)
        outs = []
        for p in range(P):
            agg_b = self._agg_bnd(hs[p], self._split_shards[p])
            rows = jnp.arange(oc)[:, None]
            agg = jnp.where(rows < int(self.n_int[p]), agg_i[p], agg_b)
            out = self_t[p] + agg @ layers[p].w_neigh + layers[p].b
            if activate:
                out = jax.nn.relu(out)
            # owned rows back into the padded local space; trash row stays 0
            outs.append(jnp.zeros((self.max_nodes, out.shape[-1]),
                                  out.dtype).at[:oc].set(out))
        return outs

    def _full_forward_overlap(self, params_list: list) -> list:
        P = self.num_parts
        hs = [self.features[p] for p in range(P)]
        num_layers = len(params_list[0].layers)
        for i in range(num_layers):
            hs = self._split_layer(hs, [p.layers[i] for p in params_list],
                                   i < num_layers - 1)
        return hs

    def _eval(self, params_list: list, split: str):
        import time

        t0 = time.perf_counter()
        logits = self._full_forward(params_list)
        micros, preds = [], []
        for p in range(self.num_parts):
            pr = jnp.argmax(logits[p], axis=-1)
            lab = jnp.where(jnp.asarray(self.masks[split][p]),
                            self.labels[p], -1)
            micro, _, _ = f1_scores_jnp(pr, lab, self.num_classes)
            micros.append(micro)
            preds.append(pr)
        out = jnp.stack(micros), jnp.stack(preds)
        jax.block_until_ready(out)
        self.last_eval_seconds = time.perf_counter() - t0
        return out

    # ------------------------------------------------------- public surface
    def phase0_epoch(self, params, opt_state, batches):
        import time

        P = self.num_parts
        leaves = jax.tree_util.tree_leaves(batches)
        iters = leaves[0].shape[0]
        # warm the jit caches on the first iteration's shapes (results
        # discarded — the functions are pure) so the timed window below
        # excludes XLA compilation, matching the SPMD engine's AOT contract
        b0 = jax.tree.map(lambda x: x[0, 0], batches)
        _, g0 = self._grad_step(params, b0)
        z = jax.tree.map(lambda g: jnp.stack([g] * P), g0)
        topk = self.grad_compress == "topk"
        if topk:
            res = self._grad_residual(params)
            jax.block_until_ready(self._apply_topk(params, opt_state, z, res))
        else:
            jax.block_until_ready(self._apply_grads(params, opt_state, z))

        t0 = time.perf_counter()
        all_losses = []
        for it in range(iters):
            losses, grads = [], []
            for p in range(P):
                b = jax.tree.map(lambda x: x[it, p], batches)
                l, g = self._grad_step(params, b)
                losses.append(l)
                grads.append(g)
            # deterministic all-reduce (stack then axis-0 sum, / P — the same
            # reduction the stacked engine performs) + jitted update
            stacked = jax.tree.map(lambda *gs: jnp.stack(gs), *grads)
            if topk:
                params, opt_state, res = self._apply_topk(
                    params, opt_state, stacked, res)
            else:
                params, opt_state = self._apply_grads(params, opt_state,
                                                      stacked)
            all_losses.append(jnp.stack(losses))
        jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        if topk:
            self._grad_res = res
        val_micro, _ = self._eval([params] * P, "val")
        return params, opt_state, jnp.stack(all_losses), val_micro, dt

    def phase0_epoch_async(self, params, opt_state, keys):
        """Python-loop reference for the fused on-device generalization
        epoch: the SAME per-partition PRNG programs (epoch draw, fanout
        sampling, feature gather) executed one partition at a time, the
        all-reduce as the deterministic stack-and-sum, and the validation
        eval as the explicit Python-loop forward — the parity oracle for
        SPMDEngine.phase0_epoch_async (DESIGN.md §7)."""
        import time

        if self._device_sampler is None:
            raise ValueError("phase0_epoch_async needs set_device_sampler()")
        ds = self._device_sampler
        # a feat-store sampler gathers through [hot | staged cold]; pass its
        # host cold table exactly when the sampler was built with the store
        ck = ({} if getattr(ds, "cold_host", None) is None
              else {"cold": jnp.asarray(ds.cold_host)})
        P = self.num_parts
        iters = ds.num_batches
        # per-partition epoch draws, in the engine's exact key order:
        # kd (draw) then ke split into per-iteration batch keys
        drawn = []
        for p in range(P):
            kd, ke = jax.random.split(keys[p])
            nodes, valid = ds.draw_epoch(kd, ds.logp[p], ds.train_idx[p],
                                         ds.k[p])
            drawn.append((nodes, valid, jax.random.split(ke, iters)))
        # warm the jit caches on the first iteration's shapes (results
        # discarded — the functions are pure) so the timed window excludes
        # XLA compilation, matching the engine's AOT contract
        b0 = ds.make_batch(drawn[0][2][0], drawn[0][0][0], drawn[0][1][0],
                           **ck)
        _, g0 = self._grad_step(params, b0)
        z = jax.tree.map(lambda g: jnp.stack([g] * P), g0)
        topk = self.grad_compress == "topk"
        if topk:
            res = self._grad_residual(params)
            jax.block_until_ready(self._apply_topk(params, opt_state, z, res))
        else:
            jax.block_until_ready(self._apply_grads(params, opt_state, z))

        t0 = time.perf_counter()
        all_losses = []
        for it in range(iters):
            losses, grads = [], []
            for p in range(P):
                nodes, valid, iter_keys = drawn[p]
                b = ds.make_batch(iter_keys[it], nodes[it], valid[it], **ck)
                l, g = self._grad_step(params, b)
                losses.append(l)
                grads.append(g)
            stacked = jax.tree.map(lambda *gs: jnp.stack(gs), *grads)
            if topk:
                params, opt_state, res = self._apply_topk(
                    params, opt_state, stacked, res)
            else:
                params, opt_state = self._apply_grads(params, opt_state,
                                                      stacked)
            all_losses.append(jnp.stack(losses))
        if topk:
            self._grad_res = res
        # the fused program's eval is part of the one device call: include
        # it in the timed window (unlike phase0_epoch, whose eval is a
        # separate call excluded from the train timing)
        val_micro, _ = self._eval([params] * P, "val")
        jax.block_until_ready(val_micro)
        dt = time.perf_counter() - t0
        self.last_eval_seconds = 0.0    # eval is inside dt on this path
        return params, opt_state, jnp.stack(all_losses), val_micro, dt

    def phase0_fullgraph_epoch(self, params, opt_state, iters: int = 1):
        """Full-graph phase-0, legibly: partition p's loss is the train-mask
        cross-entropy of ITS rows of the full multi-partition forward (the
        same Python-loop forward `_eval` uses), differentiated with plain
        ``jax.grad`` — the parity oracle for the engines' fused
        ``value_and_grad`` through the halo exchange and the aggregation
        op's custom VJP."""
        import time

        from functools import partial

        if self.halo_cache:
            raise ValueError(
                "halo_cache is an eval-forward optimisation; full-graph "
                "training differentiates through the live halo exchange "
                "and cannot train against stale cached embeddings")
        if self.grad_compress == "topk":
            raise ValueError(
                "top-k gradient sparsification is a sampled phase-0 feature; "
                "full-graph training keeps the exact (or bucketed) all-reduce")

        from ..train.losses import cross_entropy_loss, focal_loss

        P = self.num_parts
        if not hasattr(self, "_fg_step"):
            labels = self.labels
            train_m = jnp.asarray(self.masks["train"])
            base_loss = (partial(focal_loss, gamma=2.0)
                         if self._fg_loss_kind == "focal"
                         else cross_entropy_loss)
            # training differentiates through the LIVE uncompressed exchange
            # even when halo_compress is on (the engine's self.fwd does the
            # same); only eval forwards quantize
            fg_fwd = (self._full_forward_overlap if self.overlap
                      else self._full_forward_plain)

            def loss_p(prm, p):
                logits = fg_fwd([prm] * P)
                return base_loss(logits[p], labels[p], mask=train_m[p])

            @jax.jit
            def fg_step(params, opt_state):
                losses, grads = [], []
                for p in range(P):
                    l, g = jax.value_and_grad(loss_p)(params, p)
                    losses.append(l)
                    grads.append(g)
                stacked = jax.tree.map(lambda *gs: jnp.stack(gs), *grads)
                # inner jit inlines under this trace: same fused arithmetic
                params, opt_state = self._apply_grads(params, opt_state,
                                                      stacked)
                return params, opt_state, jnp.stack(losses)

            self._fg_step = fg_step

        # compile warm-up outside the timed window (pure, result discarded)
        jax.block_until_ready(self._fg_step(params, opt_state))
        t0 = time.perf_counter()
        all_losses = []
        for _ in range(iters):
            params, opt_state, losses = self._fg_step(params, opt_state)
            all_losses.append(losses)
        jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        val_micro, _ = self._eval([params] * P, "val")
        return params, opt_state, jnp.stack(all_losses), val_micro, dt

    def phase1_epoch(self, pparams, popt, batches, global_params, budgets):
        import time

        P = self.num_parts
        leaves = jax.tree_util.tree_leaves(batches)
        iters = leaves[0].shape[0]
        budgets = np.asarray(budgets)
        if budgets.dtype == bool:        # pre-async API: full epoch or zero
            budgets = np.where(budgets, iters, 0)
        pp = [jax.tree.map(lambda x: x[p], pparams) for p in range(P)]
        po = [jax.tree.map(lambda x: x[p], popt) for p in range(P)]
        # compile warm-up outside the timed window (pure, results discarded)
        jax.block_until_ready(self._pstep1(
            pp[0], po[0], jax.tree.map(lambda x: x[0, 0], batches),
            global_params, jnp.asarray(budgets[0] > 0)))

        t0 = time.perf_counter()
        all_losses = []
        for it in range(iters):
            losses = []
            for p in range(P):
                b = jax.tree.map(lambda x: x[it, p], batches)
                # the masked scan's semantics, legibly: partition p trains
                # while it < its own budget, is frozen bitwise afterwards
                pp[p], po[p], l = self._pstep1(pp[p], po[p], b, global_params,
                                              jnp.asarray(it < budgets[p]))
                losses.append(l)
            all_losses.append(jnp.stack(losses))
        jax.block_until_ready(pp)
        dt = time.perf_counter() - t0
        val_micro, _ = self._eval(pp, "val")
        from .stacking import stack_pytrees
        return (stack_pytrees(pp), stack_pytrees(po),
                jnp.stack(all_losses), val_micro, dt)

    # ----------------------------------------------- async personalization
    def set_device_sampler(self, sampler) -> None:
        self._device_sampler = sampler

    def phase1_epoch_async(self, pparams, popt, keys, budgets, global_params):
        """Python-loop reference for the on-device async path: the SAME
        per-partition PRNG programs (mini-epoch draw, fanout sampling,
        feature gather), executed one partition at a time — the parity
        oracle for SPMDEngine.phase1_epoch_async."""
        import time

        if self._device_sampler is None:
            raise ValueError("phase1_epoch_async needs set_device_sampler()")
        ds = self._device_sampler
        ck = ({} if getattr(ds, "cold_host", None) is None
              else {"cold": jnp.asarray(ds.cold_host)})
        P = self.num_parts
        budgets = np.asarray(budgets)
        iters = ds.num_batches
        pp = [jax.tree.map(lambda x: x[p], pparams) for p in range(P)]
        po = [jax.tree.map(lambda x: x[p], popt) for p in range(P)]

        t0 = time.perf_counter()
        all_losses = []
        for p in range(P):
            kd, ke = jax.random.split(keys[p])
            nodes, valid = ds.draw_epoch(kd, ds.logp[p], ds.train_idx[p],
                                         ds.k[p])
            iter_keys = jax.random.split(ke, iters)
            losses = []
            for it in range(iters):
                batch = ds.make_batch(iter_keys[it], nodes[it], valid[it],
                                      **ck)
                pp[p], po[p], l = self._pstep1(
                    pp[p], po[p], batch, global_params,
                    jnp.asarray(it < budgets[p]))
                losses.append(l)
            all_losses.append(jnp.stack(losses))
        jax.block_until_ready(pp)
        dt = time.perf_counter() - t0
        val_micro, _ = self._eval(pp, "val")
        from .stacking import stack_pytrees
        return (stack_pytrees(pp), stack_pytrees(po),
                jnp.stack(all_losses, axis=1), val_micro, dt)

    def evaluate(self, params, split: str = "test",
                 per_partition_params: bool = True):
        P = self.num_parts
        if per_partition_params:
            plist = [jax.tree.map(lambda x: x[p], params) for p in range(P)]
        else:
            plist = [params] * P
        return self._eval(plist, split)

    # ---- checkpoint/resume surface (mirrors SPMDEngine) ------------------
    def halo_cache_state(self):
        """(cache pytree, age) for checkpointing; None without the cache."""
        if not self.halo_cache:
            return None
        return self._halo_state, self._halo_age

    def restore_halo_cache_state(self, state, age: int) -> None:
        if not self.halo_cache:
            raise ValueError("engine built without halo_cache")
        self._halo_state = jax.tree.map(
            lambda x: jnp.asarray(x, self._halo_dtype), state)
        self._halo_age = int(age)

    # -------------------------------------- compressed communication state
    @property
    def halo_wire_bytes_per_layer(self) -> int:
        """Real payload bytes ONE layer's halo exchange puts on the wire
        under the configured compression (mirrors SPMDEngine)."""
        return self._halo_rows_total * wire_row_bytes(
            self._halo_row_width, self.halo_compress, self._halo_itemsize)

    def _grad_residual(self, params):
        """Lazily-built (P, N) top-k error-feedback state, zero before the
        first compressed sync (mirrors SPMDEngine)."""
        if self._grad_res is None:
            from jax.flatten_util import ravel_pytree

            flat, _ = ravel_pytree(params)
            self._grad_res = jnp.zeros((self.num_parts, flat.shape[0]),
                                       flat.dtype)
        return self._grad_res

    def comm_residual_state(self):
        """``(halo_residual, grad_residual)`` for checkpointing; each entry
        None when the matching compression is off (or, for top-k, before
        the first phase-0 step).  None when neither exists."""
        h = self._halo_residual if self.halo_compress != "none" else None
        g = self._grad_res if self.grad_compress == "topk" else None
        if h is None and g is None:
            return None
        return h, g

    def restore_comm_residual_state(self, state) -> None:
        h, g = state
        if h is not None:
            self._halo_residual = jax.tree.map(
                lambda x: jnp.asarray(x, self._halo_dtype), h)
        if g is not None:
            self._grad_res = jnp.asarray(g)
