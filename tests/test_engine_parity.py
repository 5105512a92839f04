"""SPMD engine parity harness (the tentpole's self-verification).

1. float64 bit-for-bit: the fused SPMD engine (stacked vmap mode) reproduces
   the sequential per-partition reference EXACTLY — losses, updated params,
   per-partition validation micro-F1 and test predictions — across
   seeds × {ew, metis, random} × {cbs, uniform}.  Runs in a subprocess so
   ``jax_enable_x64`` cannot leak into other tests.
2. Budget parity: random per-partition iteration budgets (including 0 and
   full-epoch) through the masked variable-length scan reproduce the
   sequential per-partition loops bit-for-bit in fp64; an all-zero budget
   step leaves params AND optimizer state bitwise unchanged.
3. Async-path parity: the fully-on-device phase-1 (device CBS draw + fanout
   + gather inside the fused step) matches the sequential reference running
   the SAME PRNG programs one partition at a time, bit-for-bit in fp64.
   Likewise the fused phase-0 program (epoch draw + train scan + FUSED
   validation eval, with and without CBS) — stacked in the shared
   subprocess, AND under shard_map on a real 4-device mesh (bitwise there
   too: its only collectives are data movement, no pmean), with the fused
   eval bitwise equal to a standalone evaluate().
4. shard_map mode: with 4 forced host devices the mesh engine matches the
   stacked engine to collective-reduction rounding (<= a few f32 ulps).
5. Pallas on the hot path: the distributed eval forward demonstrably stages
   ``segment_agg`` (trace-time call counter) and agrees with the jnp
   segment-op reference.
6. segment_agg property sweep: Pallas vs ref over ragged degree
   distributions — power-law, isolated nodes, single giant hub.
7. Full-graph training: phase-0 ``value_and_grad`` through the distributed
   forward (halo-exchange VJP + the custom-VJP aggregation op) matches the
   sequential reference bit-for-bit in fp64, and the Pallas path stages the
   forward AND transpose kernels while matching the jnp path in f32.
8. Historical halo cache: staleness 0 (refresh every eval) == the sync
   forward bitwise (stacked AND real spmd mesh); cached mode == the
   sequential stale-aggregation oracle AND an independent closed-form stale
   oracle bitwise in fp64 (standalone evaluate AND the fused async epoch);
   comm counters report only the refreshed-row payload (CV chunks partition
   one full exchange); the pure-cached spmd program lowers with no
   all_to_all at all.
9. Compressed communication (PR-9): error-compensated fp16/int8 halo
   quantization and bucketed/top-k gradient reduction each match the
   sequential fp64 oracle bit-for-bit (the oracle models the quantize /
   dequantize / residual arithmetic exactly); compress=off stays bitwise
   the pre-PR-9 forward; ring schedules and the halo cache compose.
10. Two-tier feature store (PR-10): the feat-store engine (hot rows
   resident, cold rows staged from the host per compiled call) equals the
   all-resident engine bit-for-bit — sync phases, hot_frac extremes, the
   fused async epochs with a feat-store device sampler, and the halo-cache
   / int8 compositions — in BOTH stacked and real-mesh shard_map modes;
   hot_frac=1.0 stages zero cold bytes.

Flaky-surface hardening: ALL fast fp64 checks (1–3) share ONE subprocess
per module (one interpreter + one set of XLA compilations), and every
subprocess enables the persistent compilation cache under ``.jax_cache/``
so reruns skip compilation entirely.
"""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jax_cache import CACHE_PRELUDE, subprocess_env

SUBPROC_ENV = subprocess_env()

# --------------------------------------------------------------------------
# shared harness body (runs inside the test process AND inside subprocesses)
# --------------------------------------------------------------------------

HARNESS = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import partition_graph, GPHyperParams, broadcast_to_partitions
from repro.core.sampler import CBSampler, build_device_epoch_sampler
from repro.engine import (EngineConfig, SPMDEngine, SequentialReference,
                          stack_epoch_batches)
from repro.graph import (BENCHMARKS, GraphSAGE, NeighborSampler,
                         build_partitioned_graph, make_benchmark)
from repro.train.optim import AdamW

P = 4
BATCH = 32

def build_case(method, seed, use_cbs, dtype):
    g = make_benchmark(BENCHMARKS["tiny"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, P,
                        method=method, seed=seed)
    pg = build_partitioned_graph(g, r.parts, P)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    loss_fn = model.make_loss_fn()
    opt = AdamW(lr=3e-3, grad_clip=5.0)
    neigh = NeighborSampler(g, fanouts=(3, 3), seed=seed)
    host_train = [g.train_idx[r.parts[g.train_idx] == p] for p in range(P)]
    samplers = [CBSampler(g.indptr, g.indices, g.labels, host_train[p],
                          batch_size=BATCH,
                          subset_fraction=0.25 if use_cbs else 1.0,
                          class_balanced=use_cbs, seed=seed + p)
                for p in range(P)]
    feats = np.asarray(g.features, dtype)

    def make_batch(nodes):
        k = len(nodes)
        if k < BATCH:
            nodes = np.concatenate([nodes, np.zeros(BATCH - k, nodes.dtype)])
        mask = np.zeros(BATCH, dtype)
        mask[:k] = 1
        b = neigh.sample(nodes)
        x_t, x_1, x_2 = b.feature_views(feats)
        return {"x_t": jnp.asarray(x_t), "x_1": jnp.asarray(x_1),
                "x_2": jnp.asarray(x_2),
                "labels": jnp.asarray(g.labels[nodes]),
                "mask": jnp.asarray(mask)}

    return g, pg, model, loss_fn, opt, samplers, make_batch, host_train


def tree_maxdiff(a, b):
    return max(float(jnp.abs(x - y).max())
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def run_pair(engA, engB, model, opt, samplers, make_batch, seed, dtype,
             budgets=None):
    '''One phase-0 epoch + one phase-1 epoch + test eval through both
    engines on IDENTICAL batches; returns max diffs.  ``budgets`` defaults
    to the pre-async gate (one frozen partition, full epoch elsewhere).'''
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(seed))
    opt_state = opt.init(params)
    b0, _, _ = stack_epoch_batches(samplers, make_batch, P)
    pA, oA, lA, vA, _ = engA.phase0_epoch(params, opt_state, b0)
    pB, oB, lB, vB, _ = engB.phase0_epoch(params, opt_state, b0)
    d = {"p0_loss": float(np.abs(np.asarray(lA) - np.asarray(lB)).max()),
         "p0_val": float(np.abs(np.asarray(vA) - np.asarray(vB)).max()),
         "p0_params": tree_maxdiff(pA, pB)}
    pp = broadcast_to_partitions(pA, P)
    po = jax.vmap(opt.init)(pp)
    b1, _, _ = stack_epoch_batches(samplers, make_batch, P)
    iters = jax.tree_util.tree_leaves(b1)[0].shape[0]
    if budgets is None:
        active = np.ones(P, bool)
        active[seed % P] = False      # one frozen host: gate parity too
        budgets = np.where(active, iters, 0)
    budgets = jnp.asarray(np.asarray(budgets, np.int32))
    ppA, poA, l1A, v1A, _ = engA.phase1_epoch(pp, po, b1, pA, budgets)
    ppB, poB, l1B, v1B, _ = engB.phase1_epoch(pp, po, b1, pB, budgets)
    d.update({"p1_loss": float(np.abs(np.asarray(l1A) - np.asarray(l1B)).max()),
              "p1_val": float(np.abs(np.asarray(v1A) - np.asarray(v1B)).max()),
              "p1_params": tree_maxdiff(ppA, ppB),
              "p1_opt": tree_maxdiff(poA, poB)})
    mA, prA = engA.evaluate(ppA, "test")
    mB, prB = engB.evaluate(ppB, "test")
    d["test_micro"] = float(np.abs(np.asarray(mA) - np.asarray(mB)).max())
    d["test_pred_mismatch"] = int((np.asarray(prA) != np.asarray(prB)).sum())
    return d


def budget_vectors(iters, seed):
    '''The satellite's budget sweep: all-zero, all-full, and random mixed
    vectors that include a 0 and a full-epoch entry.'''
    rng = np.random.default_rng(seed)
    mixed = rng.integers(0, iters + 1, P)
    mixed[rng.integers(0, P)] = 0
    mixed[(rng.integers(0, P - 1) + np.argmin(mixed) + 1) % P] = iters
    return {"zero": np.zeros(P, np.int64),
            "full": np.full(P, iters, np.int64),
            "mixed": mixed}


def run_budget_parity(eng, seq, model, opt, samplers, make_batch, seed, dtype):
    '''Masked-scan budget parity (engine vs sequential, bit-for-bit) plus
    the all-zero-budget no-op check (params AND opt state bitwise).'''
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(seed))
    pp = broadcast_to_partitions(params, P)
    po = jax.vmap(opt.init)(pp)
    b1, _, _ = stack_epoch_batches(samplers, make_batch, P)
    iters = jax.tree_util.tree_leaves(b1)[0].shape[0]
    out = {}
    for tag, bud in budget_vectors(iters, seed).items():
        budj = jnp.asarray(bud.astype(np.int32))
        ppA, poA, lA, vA, _ = eng.phase1_epoch(pp, po, b1, params, budj)
        ppB, poB, lB, vB, _ = seq.phase1_epoch(pp, po, b1, params, budj)
        out[f"{tag}_params"] = tree_maxdiff(ppA, ppB)
        out[f"{tag}_opt"] = tree_maxdiff(poA, poB)
        out[f"{tag}_loss"] = float(np.abs(np.asarray(lA) - np.asarray(lB)).max())
        out[f"{tag}_val"] = float(np.abs(np.asarray(vA) - np.asarray(vB)).max())
        if tag == "zero":
            out["zero_noop_params"] = tree_maxdiff(ppA, pp)
            out["zero_noop_opt"] = tree_maxdiff(poA, po)
    return out


def run_overlap_parity(pg, model, loss_fn, opt, samplers, make_batch, seed,
                       dtype):
    '''Boundary/interior split forward parity (the PR-3 tentpole):
      1. overlapped stacked engine == overlapped sequential reference,
         bit-for-bit through run_pair (phases + eval);
      2. overlapped == SYNCHRONOUS forward bit-for-bit on owned rows
         (micro-F1 over the owned masks must match exactly; halo/pad
         logit rows are not meaningful in either forward);
      3. the chunked ppermute ring delivers bit-identical results to the
         single all_to_all exchange.'''
    from repro.engine import SequentialReference, SPMDEngine
    kw = dict(mode="stacked", use_pallas_agg=False, dtype=dtype)
    engO = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                      EngineConfig(overlap_halo=True, **kw))
    seqO = SequentialReference(model, loss_fn, opt, pg, GPHyperParams(),
                               EngineConfig(overlap_halo=True, **kw))
    d = {"seq_" + k: v for k, v in run_pair(
        engO, seqO, model, opt, samplers, make_batch, seed, dtype).items()}

    engS = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                      EngineConfig(**kw))
    engR = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                      EngineConfig(overlap_halo=True, ring_chunks=3, **kw))
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(seed))
    pp = broadcast_to_partitions(params, P)
    for split in ("val", "test"):
        mS, prS = engS.evaluate(pp, split)
        mO, prO = engO.evaluate(pp, split)
        mR, prR = engR.evaluate(pp, split)
        prS, prO, prR = map(np.asarray, (prS, prO, prR))
        d[f"{split}_micro"] = float(np.abs(np.asarray(mS) - np.asarray(mO)).max())
        d[f"{split}_pred_owned"] = int(sum(
            (prS[p, : pg.n_own[p]] != prO[p, : pg.n_own[p]]).sum()
            for p in range(P)))
        d[f"{split}_ring_micro"] = float(np.abs(np.asarray(mR) - np.asarray(mO)).max())
        d[f"{split}_ring_pred"] = int((prR != prO).sum())
    return d


def run_fullgraph_parity(eng, seq, model, opt, seed, dtype, iters=2):
    '''Full-graph phase-0 (value_and_grad THROUGH the distributed forward:
    halo exchange VJP + the aggregation op) — fused engine vs the
    sequential reference differentiating the Python-loop forward.'''
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(seed))
    opt_state = opt.init(params)
    pA, oA, lA, vA, _ = eng.phase0_fullgraph_epoch(params, opt_state, iters)
    pB, oB, lB, vB, _ = seq.phase0_fullgraph_epoch(params, opt_state, iters)
    return {"loss": float(np.abs(np.asarray(lA) - np.asarray(lB)).max()),
            "val": float(np.abs(np.asarray(vA) - np.asarray(vB)).max()),
            "params": tree_maxdiff(pA, pB),
            "opt": tree_maxdiff(oA, oB)}


def run_phase0_async_parity(eng, seq, g, host_train, model, opt, seed, dtype):
    '''Fused phase-0 device program (on-device epoch draw + synchronous
    train scan with the cross-partition gradient mean + the FUSED validation
    eval) vs the sequential oracle running the SAME PRNG programs — for the
    CBS-weighted draw AND the uniform no-CBS shuffle — plus the fused-eval
    == standalone evaluate() bitwise check.'''
    out = {}
    for tag, cbs in (("cbs", True), ("uni", False)):
        ds = build_device_epoch_sampler(g, host_train, P, batch_size=BATCH,
                                        subset_fraction=0.25 if cbs else 1.0,
                                        class_balanced=cbs, fanouts=(3, 3),
                                        dtype=dtype)
        eng.set_device_sampler(ds)
        seq.set_device_sampler(ds)
        params = jax.tree.map(lambda x: jnp.asarray(x, dtype),
                              model.init(seed))
        opt_state = opt.init(params)
        keys = jax.random.split(jax.random.PRNGKey(seed ^ 0x6E02), P)
        pA, oA, lA, vA, _ = eng.phase0_epoch_async(params, opt_state, keys)
        pB, oB, lB, vB, _ = seq.phase0_epoch_async(params, opt_state, keys)
        out[f"{tag}_params"] = tree_maxdiff(pA, pB)
        out[f"{tag}_opt"] = tree_maxdiff(oA, oB)
        out[f"{tag}_loss"] = float(np.abs(np.asarray(lA)
                                          - np.asarray(lB)).max())
        out[f"{tag}_val"] = float(np.abs(np.asarray(vA)
                                         - np.asarray(vB)).max())
        mS, _ = eng.evaluate(pA, "val", per_partition_params=False)
        out[f"{tag}_fused_eval"] = float(np.abs(np.asarray(vA)
                                                - np.asarray(mS)).max())
    return out


def run_halo_cache_parity(pg, model, loss_fn, opt, seed, dtype):
    '''Historical halo cache parity (the PR-6 tentpole):
      1. staleness 0 (K=1): the cached engine == the sync forward bitwise
         across a sequence of evals with changing params, every eval paying
         the full exchange;
      2. K=3, cv off/on: cached stacked engine == cached sequential oracle
         bitwise across the eval sequence, with equal byte counters;
      3. counters: full-refresh evals report 2*halo_bytes_per_layer, pure-
         cached evals 0, and the CV chunk payloads sum to one full exchange
         over a refresh cycle;
      4. an INDEPENDENT closed-form stale oracle (cv off): at eval t the h1
         halo rows must equal layer-1 outputs under the params of the last
         full refresh r = (t // K) * K — derived with no incremental cache
         state, so a shared off-by-one in engine + sequential cannot hide.'''
    from repro.graph.distributed import make_ref_mean_agg

    kw = dict(mode="stacked", use_pallas_agg=False, dtype=dtype)
    mk = lambda **o: SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                                EngineConfig(**kw, **o))
    mkseq = lambda **o: SequentialReference(model, loss_fn, opt, pg,
                                            GPHyperParams(),
                                            EngineConfig(**kw, **o))
    base = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(seed))
    pseq = [jax.tree.map(lambda x: x * (1.0 + 0.05 * i), base)
            for i in range(6)]
    full = 2 * pg.halo_bytes_per_layer
    out = {}

    sync = mk()
    k1 = mk(halo_cache=True, halo_refresh_every=1)
    d = b = 0
    for prm in pseq[:3]:
        mS, prS = sync.evaluate(prm, "val", per_partition_params=False)
        mC, prC = k1.evaluate(prm, "val", per_partition_params=False)
        d = max(d, float(jnp.abs(mS - mC).max()),
                float((np.asarray(prS) != np.asarray(prC)).sum()))
        b += int(k1.last_halo_exchange_bytes != full)
    out["staleness0"] = d
    out["staleness0_bytes"] = float(b)

    for tag, cv in (("plain", False), ("cv", True)):
        eng = mk(halo_cache=True, halo_refresh_every=3, halo_cv=cv)
        seq = mkseq(halo_cache=True, halo_refresh_every=3, halo_cv=cv)
        d = b = 0
        byte_seq = []
        for prm in pseq:
            mA, prA = eng.evaluate(prm, "val", per_partition_params=False)
            mB, prB = seq.evaluate(prm, "val", per_partition_params=False)
            d = max(d, float(jnp.abs(mA - mB).max()),
                    float((np.asarray(prA) != np.asarray(prB)).sum()))
            b += int(eng.last_halo_exchange_bytes
                     != seq.last_halo_exchange_bytes)
            byte_seq.append(eng.last_halo_exchange_bytes)
        out[f"{tag}_vs_seq"] = d
        out[f"{tag}_bytes_mismatch"] = float(b)
        if cv:
            out["cv_cycle"] = float(byte_seq[0] != full
                                    or sum(byte_seq[1:3]) != full
                                    or byte_seq[3] != full
                                    or 0 in byte_seq[1:3])
        else:
            out["plain_cached_bytes"] = float(
                byte_seq[0] != full or byte_seq[1] != 0
                or byte_seq[2] != 0 or byte_seq[3] != full)

    send_idx = jnp.asarray(pg.send_idx)
    send_mask = jnp.asarray(pg.send_mask, dtype)
    recv_pos = jnp.asarray(pg.recv_pos)
    feats = jnp.asarray(pg.features, dtype)
    agg = make_ref_mean_agg(pg.max_nodes)
    shards = [{"edge_src": jnp.asarray(pg.edge_src[p]),
               "edge_dst": jnp.asarray(pg.edge_dst[p]),
               "edge_mask": jnp.asarray(pg.edge_mask[p], dtype)}
              for p in range(P)]

    def exchange(hs):
        sent = [hs[p][send_idx[p]] * send_mask[p][..., None]
                for p in range(P)]
        res = []
        for q in range(P):
            recv = jnp.stack([sent[p][q] for p in range(P)])
            res.append(hs[q].at[recv_pos[q].reshape(-1)].set(
                recv.reshape(-1, hs[q].shape[-1])))
        return res

    def layer1(prm, hs):
        return [jax.nn.relu(hs[p] @ prm.layer1.w_self
                            + agg(hs[p], shards[p]) @ prm.layer1.w_neigh
                            + prm.layer1.b) for p in range(P)]

    # h0 never goes stale in VALUE: features are constant, so the cached
    # feature-halo rows equal a live exchange and the whole staleness story
    # lives in the h1 halo rows
    hs = exchange([feats[p] for p in range(P)])
    eng = mk(halo_cache=True, halo_refresh_every=3)
    d = 0
    for t, prm in enumerate(pseq):
        _, prA = eng.evaluate(prm, "val", per_partition_params=False)
        h1_cur = layer1(prm, hs)
        h1_stale = layer1(pseq[(t // 3) * 3], hs)
        sent = [h1_stale[p][send_idx[p]] * send_mask[p][..., None]
                for p in range(P)]
        preds = []
        for q in range(P):
            recv = jnp.stack([sent[p][q] for p in range(P)])
            h1 = h1_cur[q].at[recv_pos[q].reshape(-1)].set(
                recv.reshape(-1, h1_cur[q].shape[-1]))
            logits = (h1 @ prm.layer2.w_self
                      + agg(h1, shards[q]) @ prm.layer2.w_neigh
                      + prm.layer2.b)
            preds.append(jnp.argmax(logits, axis=-1))
        d = max(d, float((np.asarray(prA)
                          != np.asarray(jnp.stack(preds))).sum()))
    out["closed_form"] = d
    return out


def run_halo_cache_async_parity(pg, g, host_train, model, loss_fn, opt,
                                seed, dtype):
    '''The cached fused async epoch (cache carried as state through the one
    device program) == the sequential oracle, bitwise, across 3 epochs at
    K=2 — exercising full-refresh AND pure-cached fused evals.'''
    kw = dict(mode="stacked", use_pallas_agg=False, dtype=dtype,
              halo_cache=True, halo_refresh_every=2)
    eng = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                     EngineConfig(**kw))
    seq = SequentialReference(model, loss_fn, opt, pg, GPHyperParams(),
                              EngineConfig(**kw))
    ds = build_device_epoch_sampler(g, host_train, P, batch_size=BATCH,
                                    subset_fraction=1.0,
                                    class_balanced=False, fanouts=(3, 3),
                                    dtype=dtype)
    eng.set_device_sampler(ds)
    seq.set_device_sampler(ds)
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(seed))
    pA = pB = params
    oA = oB = opt.init(params)
    keys0 = jax.random.split(jax.random.PRNGKey(seed ^ 0x6E02), P)
    d = b = 0
    for e in range(3):
        keys = jax.vmap(jax.random.fold_in, (0, None))(keys0, e)
        pA, oA, lA, vA, _ = eng.phase0_epoch_async(pA, oA, keys)
        pB, oB, lB, vB, _ = seq.phase0_epoch_async(pB, oB, keys)
        d = max(d, tree_maxdiff(pA, pB),
                float(np.abs(np.asarray(lA) - np.asarray(lB)).max()),
                float(np.abs(np.asarray(vA) - np.asarray(vB)).max()))
        b += int(eng.last_halo_exchange_bytes != seq.last_halo_exchange_bytes)
    return {"async_cached": d, "async_cached_bytes": float(b)}


def run_comm_compress_parity(pg, model, loss_fn, opt, samplers, make_batch,
                             seed, dtype):
    '''Compressed communication parity (the PR-9 tentpole):
      1. compressed phase-0 gradient reduction (bucketed psum spelling and
         top-k EF sparsification): compressed stacked engine == the
         sequential fp64 oracle bit-for-bit on SHARED drawn batches, and
         stacked bucketed == plain mode-none params bitwise;
      2. quantized halo eval (fp16 / int8 with carried residual feedback):
         engine eval sequence == oracle bitwise with equal byte counters,
         strictly below the uncompressed wire size; compress=off reports
         EXACTLY pg.halo_bytes_per_layer per layer (the pre-PR-9 lock);
      3. the chunked ppermute ring moves bit-identical compressed payloads
         (quantization happens BEFORE the collective);
      4. int8 composes with the PR-6 halo cache: refresh payloads quantize,
         the cache stores dequantized rows, engine == oracle bitwise.'''
    kw = dict(mode="stacked", use_pallas_agg=False, dtype=dtype)
    mk = lambda cls, **o: cls(model, loss_fn, opt, pg, GPHyperParams(),
                              EngineConfig(**kw, **o))
    out = {}
    base = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(seed))
    opt_state = opt.init(base)
    b0, _, _ = stack_epoch_batches(samplers, make_batch, P)
    pN, _, _, _, _ = mk(SPMDEngine).phase0_epoch(base, opt_state, b0)
    for gmode in ("bucketed", "topk"):
        eng = mk(SPMDEngine, grad_compress=gmode, grad_bucket_kb=1)
        seq = mk(SequentialReference, grad_compress=gmode, grad_bucket_kb=1)
        pA, oA, lA, vA, _ = eng.phase0_epoch(base, opt_state, b0)
        pB, oB, lB, vB, _ = seq.phase0_epoch(base, opt_state, b0)
        out[f"{gmode}_params"] = tree_maxdiff(pA, pB)
        out[f"{gmode}_opt"] = tree_maxdiff(oA, oB)
        out[f"{gmode}_loss"] = float(np.abs(np.asarray(lA)
                                            - np.asarray(lB)).max())
        out[f"{gmode}_val"] = float(np.abs(np.asarray(vA)
                                           - np.asarray(vB)).max())
        if gmode == "bucketed":
            out["bucketed_vs_none"] = tree_maxdiff(pA, pN)

    pseq = [jax.tree.map(lambda x: x * (1.0 + 0.05 * i), base)
            for i in range(3)]
    full = model.num_layers * pg.halo_bytes_per_layer
    out["none_wire_eq_pg"] = float(
        mk(SPMDEngine).halo_wire_bytes_per_layer != pg.halo_bytes_per_layer)
    for hmode in ("fp16", "int8"):
        eng = mk(SPMDEngine, halo_compress=hmode)
        seq = mk(SequentialReference, halo_compress=hmode)
        ring = mk(SPMDEngine, halo_compress=hmode, ring_chunks=3)
        d = ringd = bad_bytes = 0.0
        for prm in pseq:
            mA, prA = eng.evaluate(prm, "val", per_partition_params=False)
            mB, prB = seq.evaluate(prm, "val", per_partition_params=False)
            mR, prR = ring.evaluate(prm, "val", per_partition_params=False)
            d = max(d, float(jnp.abs(mA - mB).max()),
                    float((np.asarray(prA) != np.asarray(prB)).sum()))
            ringd = max(ringd, float(jnp.abs(mA - mR).max()),
                        float((np.asarray(prA) != np.asarray(prR)).sum()))
            bad_bytes += int(eng.last_halo_exchange_bytes
                             != seq.last_halo_exchange_bytes)
            bad_bytes += int(not (0 < eng.last_halo_exchange_bytes < full))
        out[f"{hmode}_eval"] = d
        out[f"{hmode}_ring"] = ringd
        out[f"{hmode}_bytes"] = bad_bytes

    engC = mk(SPMDEngine, halo_compress="int8", halo_cache=True,
              halo_refresh_every=2)
    seqC = mk(SequentialReference, halo_compress="int8", halo_cache=True,
              halo_refresh_every=2)
    d = bad_bytes = 0.0
    for prm in pseq + pseq[:1]:
        mA, prA = engC.evaluate(prm, "val", per_partition_params=False)
        mB, prB = seqC.evaluate(prm, "val", per_partition_params=False)
        d = max(d, float(jnp.abs(mA - mB).max()),
                float((np.asarray(prA) != np.asarray(prB)).sum()))
        bad_bytes += int(engC.last_halo_exchange_bytes
                         != seqC.last_halo_exchange_bytes)
    out["cached_int8"] = d
    out["cached_int8_bytes"] = bad_bytes
    return out


def run_async_parity(eng, seq, g, host_train, model, opt, seed, dtype):
    '''Fully-on-device phase-1 (device CBS draw + fanout + gather inside the
    fused step) vs the sequential reference running the SAME PRNG programs.'''
    ds = build_device_epoch_sampler(g, host_train, P, batch_size=BATCH,
                                    subset_fraction=0.25,
                                    class_balanced=True, fanouts=(3, 3),
                                    dtype=dtype)
    eng.set_device_sampler(ds)
    seq.set_device_sampler(ds)
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(seed))
    pp = broadcast_to_partitions(params, P)
    po = jax.vmap(opt.init)(pp)
    keys = jax.random.split(jax.random.PRNGKey(seed), P)
    budgets = jnp.asarray(
        np.minimum(np.arange(P), ds.num_batches).astype(np.int32))
    ppA, poA, lA, vA, _ = eng.phase1_epoch_async(pp, po, keys, budgets, params)
    ppB, poB, lB, vB, _ = seq.phase1_epoch_async(pp, po, keys, budgets, params)
    i_run = np.asarray(lA).shape[0]
    return {"params": tree_maxdiff(ppA, ppB),
            "opt": tree_maxdiff(poA, poB),
            "loss": float(np.abs(np.asarray(lA)
                                 - np.asarray(lB)[:i_run]).max()),
            "val": float(np.abs(np.asarray(vA) - np.asarray(vB)).max())}


def run_featstore_parity(pg, g, host_train, model, loss_fn, opt, samplers,
                         make_batch, seed, dtype):
    '''Two-tier feature store parity (the PR-10 tentpole):
      1. sync phases + test eval: the feat-store engine (hot rows resident,
         cold rows staged host-side per compiled call) == the all-resident
         engine bit-for-bit through run_pair;
      2. hot_frac extremes: 0.0 (everything staged) and 1.0 (everything
         resident, ZERO cold bytes) both reproduce the resident eval;
      3. compositions: feat_store x PR-6 halo cache and feat_store x PR-9
         int8 halo quantization each == the same composition all-resident;
      4. the fully-fused async epochs (phase-0 epoch program and phase-1
         budgeted scan) with a feat-store device sampler == the all-resident
         sampler running the SAME PRNG programs.'''
    kw = dict(mode="stacked", use_pallas_agg=False, dtype=dtype)
    mk = lambda **o: SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                                EngineConfig(**kw, **o))
    out = {}
    base = mk()
    fs = mk(feat_store=True, hot_frac=0.25)
    for k, v in run_pair(fs, base, model, opt, samplers, make_batch,
                         seed, dtype).items():
        out[f"sync_{k}"] = v
    params = jax.tree.map(lambda x: jnp.asarray(x, dtype), model.init(seed))
    pseq = [jax.tree.map(lambda x: x * (1.0 + 0.05 * i), params)
            for i in range(3)]
    cases = [("hot0", dict(hot_frac=0.0), {}),
             ("hot1", dict(hot_frac=1.0), {}),
             ("cache", dict(hot_frac=0.25, halo_cache=True,
                            halo_refresh_every=2),
              dict(halo_cache=True, halo_refresh_every=2)),
             ("int8", dict(hot_frac=0.25, halo_compress="int8"),
              dict(halo_compress="int8"))]
    for tag, fso, refo in cases:
        eA = mk(feat_store=True, **fso)
        eB = mk(**refo)
        d = 0.0
        for prm in pseq:
            mA, prA = eA.evaluate(prm, "val", per_partition_params=False)
            mB, prB = eB.evaluate(prm, "val", per_partition_params=False)
            d = max(d, float(jnp.abs(mA - mB).max()),
                    float((np.asarray(prA) != np.asarray(prB)).sum()))
        out[f"{tag}_eval"] = d
        if tag == "hot1":           # all-hot must never stage a cold byte
            out["hot1_cold_bytes"] = float(eA.cold_h2d_bytes)
    dsF = build_device_epoch_sampler(g, host_train, P, batch_size=BATCH,
                                     subset_fraction=0.25,
                                     class_balanced=True, fanouts=(3, 3),
                                     dtype=dtype, feat_store=True,
                                     hot_frac=0.25)
    dsR = build_device_epoch_sampler(g, host_train, P, batch_size=BATCH,
                                     subset_fraction=0.25,
                                     class_balanced=True, fanouts=(3, 3),
                                     dtype=dtype)
    fs.set_device_sampler(dsF)
    base.set_device_sampler(dsR)
    opt_state = opt.init(params)
    keys = jax.random.split(jax.random.PRNGKey(seed ^ 0x10FE), P)
    pA, oA, lA, vA, _ = fs.phase0_epoch_async(params, opt_state, keys)
    pB, oB, lB, vB, _ = base.phase0_epoch_async(params, opt_state, keys)
    out["p0a_params"] = tree_maxdiff(pA, pB)
    out["p0a_opt"] = tree_maxdiff(oA, oB)
    out["p0a_loss"] = float(np.abs(np.asarray(lA) - np.asarray(lB)).max())
    out["p0a_val"] = float(np.abs(np.asarray(vA) - np.asarray(vB)).max())
    pp = broadcast_to_partitions(pA, P)
    po = jax.vmap(opt.init)(pp)
    budgets = jnp.asarray(
        np.minimum(np.arange(P), dsF.num_batches).astype(np.int32))
    ppA, poA, l1A, v1A, _ = fs.phase1_epoch_async(pp, po, keys, budgets, pA)
    ppB, poB, l1B, v1B, _ = base.phase1_epoch_async(pp, po, keys, budgets, pB)
    out["p1a_params"] = tree_maxdiff(ppA, ppB)
    out["p1a_opt"] = tree_maxdiff(poA, poB)
    out["p1a_loss"] = float(np.abs(np.asarray(l1A) - np.asarray(l1B)).max())
    out["p1a_val"] = float(np.abs(np.asarray(v1A) - np.asarray(v1B)).max())
    return out
"""

# --------------------------------------------------------------------------
# ONE fp64 subprocess for the whole module: smoke parity + budget matrix +
# async-path parity share a single interpreter and compilation set
# --------------------------------------------------------------------------

FP64_SHARED_SCRIPT = (
    CACHE_PRELUDE
    + "jax.config.update('jax_enable_x64', True)\n"
    + HARNESS
    + r"""
import json
out = {}
cfg = EngineConfig(mode="stacked", use_pallas_agg=False, dtype=jnp.float64)
g, pg, model, loss_fn, opt, samplers, make_batch, host_train = build_case(
    "ew", 0, True, np.float64)
eng = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(), cfg)
seq = SequentialReference(model, loss_fn, opt, pg, GPHyperParams(), cfg)
out["smoke"] = run_pair(eng, seq, model, opt, samplers, make_batch, 0,
                        jnp.float64)
out["budget"] = run_budget_parity(eng, seq, model, opt, samplers, make_batch,
                                  0, jnp.float64)
out["async"] = run_async_parity(eng, seq, g, host_train, model, opt, 0,
                                jnp.float64)
out["phase0_async"] = run_phase0_async_parity(eng, seq, g, host_train, model,
                                              opt, 0, jnp.float64)
out["overlap"] = run_overlap_parity(pg, model, loss_fn, opt, samplers,
                                    make_batch, 0, jnp.float64)
out["fullgraph"] = run_fullgraph_parity(eng, seq, model, opt, 0, jnp.float64)
cfgO = EngineConfig(mode="stacked", use_pallas_agg=False, overlap_halo=True,
                    dtype=jnp.float64)
engO = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(), cfgO)
seqO = SequentialReference(model, loss_fn, opt, pg, GPHyperParams(), cfgO)
out["fullgraph_overlap"] = run_fullgraph_parity(engO, seqO, model, opt, 0,
                                                jnp.float64)
out["halo_cache"] = run_halo_cache_parity(pg, model, loss_fn, opt, 0,
                                          jnp.float64)
out["halo_cache_async"] = run_halo_cache_async_parity(pg, g, host_train,
                                                      model, loss_fn, opt, 0,
                                                      jnp.float64)
out["comm_compress"] = run_comm_compress_parity(pg, model, loss_fn, opt,
                                                samplers, make_batch, 0,
                                                jnp.float64)
out["featstore"] = run_featstore_parity(pg, g, host_train, model, loss_fn,
                                        opt, samplers, make_batch, 0,
                                        jnp.float64)
print("RESULTS", json.dumps(out))
"""
)


@pytest.fixture(scope="module")
def fp64_shared():
    res = subprocess.run([sys.executable, "-c", FP64_SHARED_SCRIPT],
                         capture_output=True, text=True, timeout=1800,
                         env=SUBPROC_ENV)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULTS")][0]
    return json.loads(line[len("RESULTS "):])


def test_engine_matches_sequential_fp64_smoke(fp64_shared):
    """Single-config fast variant of the bit-for-bit check (tier-1: the full
    matrix runs under -m slow)."""
    assert all(v == 0 for v in fp64_shared["smoke"].values()), fp64_shared["smoke"]


def test_budget_parity_and_zero_budget_noop_fp64(fp64_shared):
    """Random per-partition budgets (incl. 0 and full-epoch) through the
    masked scan == sequential loops bit-for-bit; an all-zero budget step is
    a bitwise no-op on params and optimizer state."""
    assert all(v == 0 for v in fp64_shared["budget"].values()), fp64_shared["budget"]


def test_async_device_sampling_parity_fp64(fp64_shared):
    """The fully-on-device async phase-1 == sequential reference running the
    same per-partition PRNG programs, bit-for-bit in fp64."""
    assert all(v == 0 for v in fp64_shared["async"].values()), fp64_shared["async"]


def test_phase0_async_parity_fp64(fp64_shared):
    """The fused phase-0 device program (epoch draw + train scan + fused
    eval) == the sequential oracle running the same PRNG programs, bit-for-
    bit in fp64, with AND without CBS; the fused eval == a standalone
    evaluate() on the resulting params, also bitwise."""
    assert all(v == 0 for v in fp64_shared["phase0_async"].values()), \
        fp64_shared["phase0_async"]


def test_overlap_split_forward_parity_fp64(fp64_shared):
    """The boundary/interior split forward: overlapped engine == overlapped
    sequential reference bit-for-bit; overlapped == synchronous forward
    bit-for-bit on owned rows (micro-F1 and owned predictions); the chunked
    ppermute ring == the all_to_all exchange bit-for-bit."""
    assert all(v == 0 for v in fp64_shared["overlap"].values()), \
        fp64_shared["overlap"]


def test_halo_cache_parity_fp64(fp64_shared):
    """Historical halo cache: staleness 0 (K=1) == the sync forward bitwise;
    K=3 (cv off AND on) cached engine == cached sequential oracle bitwise
    across a 6-eval sequence; == an independent closed-form stale oracle
    (h1 halo rows recomputed from the last-refresh params, no incremental
    cache state); comm counters report only the refreshed-row payload, with
    CV chunks summing to one full exchange per cycle."""
    assert all(v == 0 for v in fp64_shared["halo_cache"].values()), \
        fp64_shared["halo_cache"]


def test_halo_cache_async_parity_fp64(fp64_shared):
    """The cached fused phase-0 async epoch (halo cache carried as state
    through the one device program) == the sequential oracle bitwise across
    3 epochs at K=2, including the byte counters."""
    assert all(v == 0 for v in fp64_shared["halo_cache_async"].values()), \
        fp64_shared["halo_cache_async"]


def test_fullgraph_train_parity_fp64(fp64_shared):
    """Full-graph phase-0 training: the fused engine's value_and_grad
    through the distributed forward (gradients crossing partitions via the
    halo exchange's VJP) == the sequential reference differentiating the
    Python-loop forward, bit-for-bit in fp64 — for both the synchronous and
    the overlapped split forward."""
    assert all(v == 0 for v in fp64_shared["fullgraph"].values()), \
        fp64_shared["fullgraph"]
    assert all(v == 0 for v in fp64_shared["fullgraph_overlap"].values()), \
        fp64_shared["fullgraph_overlap"]


def test_comm_compress_parity_fp64(fp64_shared):
    """PR-9: quantized halo exchange (fp16/int8 with error feedback) and
    compressed gradient reduction (bucketed/top-k) match the sequential
    fp64 oracle bit-for-bit; stacked bucketed == mode none; the ppermute
    ring moves bit-identical compressed payloads; int8 composes with the
    halo cache; byte counters agree, stay positive, and sit strictly below
    the uncompressed wire size (compress=off reports exactly the old
    accounting)."""
    assert all(v == 0 for v in fp64_shared["comm_compress"].values()), \
        fp64_shared["comm_compress"]


def test_featstore_parity_fp64(fp64_shared):
    """PR-10: the two-tier feature store is bitwise invisible — training +
    eval through the feat-store engine (sync run_pair phases, hot_frac 0.0
    and 1.0 extremes, the fused async phase-0/phase-1 epochs with a
    feat-store device sampler, and the compositions with the halo cache and
    int8 halo quantization) all equal the all-resident engine bit-for-bit,
    and hot_frac=1.0 stages zero cold bytes."""
    assert all(v == 0 for v in fp64_shared["featstore"].values()), \
        fp64_shared["featstore"]


# --------------------------------------------------------------------------
# the full (slow) fp64 matrix: seeds × methods × sampler regimes, each with
# the gate smoke AND a random budget vector
# --------------------------------------------------------------------------

FP64_MATRIX_SCRIPT = (
    CACHE_PRELUDE
    + "jax.config.update('jax_enable_x64', True)\n"
    + HARNESS
    + r"""
import itertools, json
failures = {}
for method, seed, use_cbs in itertools.product(
        ("ew", "metis", "random"), (0, 1), (True, False)):
    cfg = EngineConfig(mode="stacked", use_pallas_agg=False,
                       dtype=jnp.float64)
    g, pg, model, loss_fn, opt, samplers, make_batch, host_train = build_case(
        method, seed, use_cbs, np.float64)
    eng = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(), cfg)
    seq = SequentialReference(model, loss_fn, opt, pg, GPHyperParams(), cfg)
    d = run_pair(eng, seq, model, opt, samplers, make_batch, seed, jnp.float64)
    d.update({"bud_" + k: v for k, v in run_budget_parity(
        eng, seq, model, opt, samplers, make_batch, seed, jnp.float64).items()})
    if any(v != 0 for v in d.values()):
        failures[f"{method}/seed{seed}/cbs={use_cbs}"] = d
print("FAILURES", json.dumps(failures))
"""
)


@pytest.mark.slow
def test_engine_matches_sequential_bitforbit_fp64():
    """Fused SPMD engine == sequential reference, bit-for-bit in float64,
    across partition methods, seeds, sampler regimes and budget vectors."""
    # 12 configs × (compile + run); generous timeout — a loaded host can be
    # an order of magnitude slower than the unloaded wall time
    res = subprocess.run([sys.executable, "-c", FP64_MATRIX_SCRIPT],
                         capture_output=True, text=True, timeout=5400,
                         env=SUBPROC_ENV)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("FAILURES")][0]
    assert line == "FAILURES {}", line


SPMD_SCRIPT = (
    "import os\n"
    "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
    + CACHE_PRELUDE
    + HARNESS
    + r"""
import json
g, pg, model, loss_fn, opt, samplers, make_batch, host_train = build_case(
    "ew", 0, True, np.float32)
eng = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                 EngineConfig(mode="spmd", use_pallas_agg=True))
stk = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                 EngineConfig(mode="stacked", use_pallas_agg=True))
assert eng.mode == "spmd", eng.mode
d = run_pair(eng, stk, model, opt, samplers, make_batch, 0, jnp.float32)
print("DIFFS", json.dumps(d))
"""
)


def test_spmd_shard_map_matches_stacked():
    """shard_map over a real 4-device partition mesh == single-device
    stacked vmap, up to collective-reduction rounding (few f32 ulps)."""
    res = subprocess.run([sys.executable, "-c", SPMD_SCRIPT],
                         capture_output=True, text=True, timeout=1800,
                         env=SUBPROC_ENV)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("DIFFS")][0]
    d = json.loads(line[len("DIFFS "):])
    # pmean (tree-wise collective) vs stacked jnp.sum/P, and per-device vs
    # vmapped batch reductions, may differ in the last ulp; everything
    # downstream must stay within tight float32 slack.  Micro-F1/argmax get
    # a hair of slack too: an ulp-level param drift can legitimately flip
    # the argmax of a near-tied logit pair on a handful of nodes.
    assert d["p0_loss"] <= 1e-6 and d["p1_loss"] <= 1e-5, d
    assert d["p0_params"] <= 1e-6 and d["p1_params"] <= 1e-5, d
    assert d["p0_val"] <= 5e-3 and d["p1_val"] <= 5e-3, d
    assert d["test_micro"] <= 5e-3 and d["test_pred_mismatch"] <= 3, d


SPMD_FP64_ASYNC_SCRIPT = (
    "import os\n"
    "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
    + CACHE_PRELUDE
    + "jax.config.update('jax_enable_x64', True)\n"
    + HARNESS
    + r"""
import json
g, pg, model, loss_fn, opt, samplers, make_batch, host_train = build_case(
    "ew", 0, True, np.float64)
cfg = EngineConfig(mode="spmd", use_pallas_agg=False, dtype=jnp.float64)
cfgS = EngineConfig(mode="stacked", use_pallas_agg=False, dtype=jnp.float64)
eng = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(), cfg)
assert eng.mode == "spmd", eng.mode
seq = SequentialReference(model, loss_fn, opt, pg, GPHyperParams(), cfgS)
d = run_phase0_async_parity(eng, seq, g, host_train, model, opt, 0,
                            jnp.float64)
# staleness 0 on the REAL mesh: a K=1 cached spmd engine == the sync spmd
# forward bitwise, and every eval pays the full exchange
engC = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                  EngineConfig(mode="spmd", use_pallas_agg=False,
                               dtype=jnp.float64, halo_cache=True,
                               halo_refresh_every=1))
base = jax.tree.map(lambda x: jnp.asarray(x, jnp.float64), model.init(0))
dd = bb = 0
for i in range(3):
    prm = jax.tree.map(lambda x: x * (1.0 + 0.1 * i), base)
    mS, prS = eng.evaluate(prm, "val", per_partition_params=False)
    mC, prC = engC.evaluate(prm, "val", per_partition_params=False)
    dd = max(dd, float(jnp.abs(mS - mC).max()),
             float((np.asarray(prS) != np.asarray(prC)).sum()))
    bb += int(engC.last_halo_exchange_bytes != 2 * pg.halo_bytes_per_layer)
d["spmd_staleness0"] = dd
d["spmd_staleness0_bytes"] = float(bb)
# structural wire witness: the refresh plan is a host-side constant, so the
# pure-cached spmd eval program must lower with NO all_to_all at all — the
# wire win is structural, not just a zeroed counter.  (The stacked-vmap mode
# cannot witness this: vmap resolves collectives to data movement at trace
# time.)
engD = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                  EngineConfig(mode="spmd", use_pallas_agg=False,
                               dtype=jnp.float64, halo_cache=True,
                               halo_refresh_every=4))
hlo_full = jax.jit(lambda p, c: engD._eval_spmd_cached(
    p, c, "val", False, (0, engD.max_send))).lower(
    base, engD._halo_state).as_text()
hlo_cached = jax.jit(lambda p, c: engD._eval_spmd_cached(
    p, c, "val", False, (0, 0))).lower(base, engD._halo_state).as_text()
d["hlo_collective_witness"] = float("all_to_all" not in hlo_full
                                    or "all_to_all" in hlo_cached)
# feat-store on the REAL mesh: the hot/cold split (and its halo-cache /
# int8-quantization compositions) is bitwise invisible under shard_map too —
# the staged cold tier enters the program before any collective runs
for tag, o in (("plain", {}),
               ("cache", dict(halo_cache=True, halo_refresh_every=2)),
               ("int8", dict(halo_compress="int8"))):
    eF = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                    EngineConfig(mode="spmd", use_pallas_agg=False,
                                 dtype=jnp.float64, feat_store=True,
                                 hot_frac=0.25, **o))
    assert eF.mode == "spmd", eF.mode
    eR = SPMDEngine(model, loss_fn, opt, pg, GPHyperParams(),
                    EngineConfig(mode="spmd", use_pallas_agg=False,
                                 dtype=jnp.float64, **o))
    fd = 0.0
    for i in range(3):
        prm = jax.tree.map(lambda x: x * (1.0 + 0.1 * i), base)
        mF, prF = eF.evaluate(prm, "val", per_partition_params=False)
        mR, prR = eR.evaluate(prm, "val", per_partition_params=False)
        fd = max(fd, float(jnp.abs(mF - mR).max()),
                 float((np.asarray(prF) != np.asarray(prR)).sum()))
    d[f"spmd_featstore_{tag}"] = fd
print("RESULTS", json.dumps(d))
"""
)


def test_phase0_async_spmd_parity_fp64():
    """The fused phase-0 program under shard_map on a REAL 4-device
    partition mesh == the sequential oracle, bit-for-bit in fp64 (CBS and
    uniform draws).  Bitwise across a real mesh is achievable because the
    program's only collectives are pure data movement (the epoch has no
    pmean: the gradient all-reduce is an all_gather followed by the same
    deterministic local stack-sum the oracle performs, and the fused eval's
    exchange is an all_to_all).  Also checks halo-cache staleness 0 on the
    real mesh: a K=1 cached spmd engine == the sync spmd forward bitwise."""
    res = subprocess.run([sys.executable, "-c", SPMD_FP64_ASYNC_SCRIPT],
                         capture_output=True, text=True, timeout=1800,
                         env=SUBPROC_ENV)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULTS")][0]
    d = json.loads(line[len("RESULTS "):])
    assert all(v == 0 for v in d.values()), d


# --------------------------------------------------------------------------
# Pallas segment_agg on the hot path
# --------------------------------------------------------------------------

def _build_f32_engines(use_pallas):
    from repro.core import partition_graph, GPHyperParams
    from repro.engine import EngineConfig, SPMDEngine
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS["tiny"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="ew", seed=0)
    pg = build_partitioned_graph(g, r.parts, 4)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    opt = AdamW(lr=1e-3)
    eng = SPMDEngine(model, model.make_loss_fn(), opt, pg, GPHyperParams(),
                     EngineConfig(mode="stacked", use_pallas_agg=use_pallas))
    return model, eng


def test_distributed_forward_calls_pallas_segment_agg():
    """The engine's eval forward must stage the Pallas kernel (trace-time
    call counter) and agree with the jnp segment-op reference engine."""
    from repro.core.gp.trainer import broadcast_to_partitions
    from repro.kernels import segment_agg as sa

    model, eng_pal = _build_f32_engines(use_pallas=True)
    _, eng_ref = _build_f32_engines(use_pallas=False)
    params = broadcast_to_partitions(model.init(0), 4)

    before = sa.pallas_call_count()
    micro_pal, preds_pal = eng_pal.evaluate(params, "val")
    after = sa.pallas_call_count()
    assert after > before, "segment_agg_pallas was never staged by the engine"

    micro_ref, preds_ref = eng_ref.evaluate(params, "val")
    np.testing.assert_allclose(np.asarray(micro_pal), np.asarray(micro_ref),
                               atol=1e-6)
    agree = (np.asarray(preds_pal) == np.asarray(preds_ref)).mean()
    assert agree > 0.999, f"pallas/ref argmax agreement only {agree}"


def test_fullgraph_train_through_pallas_kernel():
    """Full-graph phase-0 through the Pallas path: the train scan must stage
    the aggregation kernel in BOTH directions (forward + the custom VJP's
    transpose kernel), and the resulting parameters must match the jnp
    segment-op engine to float32 rounding."""
    import jax.numpy as jnp

    from repro.kernels import segment_agg as sa

    model, eng_pal = _build_f32_engines(use_pallas=True)
    _, eng_ref = _build_f32_engines(use_pallas=False)
    params = model.init(0)
    opt_state = eng_pal.optimizer.init(params)

    before = sa.pallas_call_count()
    pP, oP, lP, vP, _ = eng_pal.phase0_fullgraph_epoch(params, opt_state, 2)
    staged = sa.pallas_call_count() - before
    # 2 layers x (fwd + transpose-bwd) in the train trace, + the eval fwd
    assert staged >= 5, f"expected fwd AND bwd kernels staged, got {staged}"

    pR, oR, lR, vR, _ = eng_ref.phase0_fullgraph_epoch(params, opt_state, 2)
    np.testing.assert_allclose(np.asarray(lP), np.asarray(lR), atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(pP),
                    jax.tree_util.tree_leaves(pR)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # training moved the params (the step is not a no-op)
    moved = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(pP), jax.tree_util.tree_leaves(params)))
    assert moved > 0


# --------------------------------------------------------------------------
# segment_agg ragged-degree property sweep (Pallas kernel vs ref oracle)
# --------------------------------------------------------------------------

def _csr_from_degrees(deg, n, rng):
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, int(indptr[-1]))
    return indptr, indices.astype(np.int64)


def _degree_profile(kind, n, rng):
    if kind == "powerlaw":
        deg = np.minimum((1.0 / rng.power(2.0, n) - 1).astype(np.int64), 200)
        return np.maximum(deg, 0)
    if kind == "isolated":
        deg = rng.integers(0, 6, n)
        deg[rng.random(n) < 0.5] = 0          # half the graph isolated
        return deg
    if kind == "giant_hub":
        deg = rng.integers(0, 4, n)
        deg[int(rng.integers(0, n))] = 5000   # one hub spanning many blocks
        return deg
    raise ValueError(kind)


@pytest.mark.parametrize("kind", ["powerlaw", "isolated", "giant_hub"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("mean", [True, False])
def test_segment_agg_ragged_degree_sweep(kind, seed, mean):
    """Pallas blocked segment aggregation == jnp oracle on adversarial
    degree distributions (ragged blocks, empty rows, single giant hub)."""
    from repro.kernels import ops, ref

    import zlib

    rng = np.random.default_rng([seed, zlib.crc32(kind.encode())])
    n = 300
    deg = _degree_profile(kind, n, rng)
    indptr, indices = _csr_from_degrees(deg, n, rng)
    x = jnp.asarray(rng.normal(0, 1, (n, 24)).astype(np.float32))
    agg = ops.make_segment_agg(indptr, indices, mean=mean)
    got = np.asarray(agg(x))
    src = jnp.asarray(indices)
    dst = jnp.asarray(np.repeat(np.arange(n), np.diff(indptr)))
    want = np.asarray(ref.segment_agg_ref(x, src, dst, n, mean=mean))
    # hub rows sum thousands of values: scale tolerance with degree
    tol = 1e-4 * max(1.0, float(deg.max()) ** 0.5) if not mean else 2e-4
    np.testing.assert_allclose(got, want, atol=tol, rtol=2e-4)
    if mean:
        assert np.abs(got[deg == 0]).max() == 0.0  # empty rows stay zero


# --------------------------------------------------------------------------
# row-range (masked) segment_agg variant: the overlapped forward's boundary
# pass — ragged sub-ranges incl. the zero-boundary / all-boundary partitions
# --------------------------------------------------------------------------

@pytest.mark.parametrize("split_kind",
                         ["zero_boundary", "all_boundary", "mixed",
                          "unaligned_tail"])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("mean", [True, False])
def test_segment_agg_rows_ragged_range_sweep(split_kind, seed, mean):
    """``segment_agg_rows`` (blocked aggregation over a REBASED destination
    sub-range, placed at a row offset) == the jnp row-range oracle, across
    ragged range positions: empty range (zero-boundary partition), the full
    node space (all-boundary), and block-unaligned interior offsets."""
    import zlib

    from repro.kernels import ref
    from repro.kernels.segment_agg import build_edge_blocks, segment_agg_rows

    rng = np.random.default_rng([seed, zlib.crc32(split_kind.encode())])
    n = 300
    n_int = {"zero_boundary": n, "all_boundary": 0,
             "mixed": int(rng.integers(1, n - 1)),
             "unaligned_tail": n - 37}[split_kind]
    range_rows = n - n_int
    deg = rng.integers(0, 8, range_rows) if range_rows else np.zeros(0, np.int64)
    indptr = np.zeros(range_rows + 1, np.int64)
    np.cumsum(deg, out=indptr[1:])
    indices = rng.integers(0, n, int(indptr[-1])).astype(np.int64)
    x = jnp.asarray(rng.normal(0, 1, (n, 24)).astype(np.float32))

    # an empty range still yields one (all-pad) block and chunk
    blocks = build_edge_blocks(indptr, indices)
    msgs = x[jnp.asarray(blocks.src.reshape(-1))]
    got = np.asarray(segment_agg_rows(
        msgs, jnp.asarray(blocks.local_dst), jnp.asarray(blocks.mask),
        jnp.asarray(blocks.chunk_block), jnp.asarray(blocks.deg),
        row_base=n_int, num_rows=n, mean=mean))
    want = np.asarray(ref.segment_agg_rows_ref(
        x, jnp.asarray(indices),
        jnp.asarray(np.repeat(np.arange(range_rows), deg)),
        max(1, range_rows), n_int, n, mean=mean))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # rows outside [row_base, n) are exactly zero — the guarantee the
    # bitwise-safe per-row select in the overlapped forward relies on
    assert np.abs(got[:n_int]).max(initial=0.0) == 0.0


# --------------------------------------------------------------------------
# historical halo cache: in-process structural witnesses (f32)
# --------------------------------------------------------------------------

def _build_halo_engine(**halo_kw):
    from repro.core import partition_graph, GPHyperParams
    from repro.engine import EngineConfig, SPMDEngine
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS["tiny"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="ew", seed=0)
    pg = build_partitioned_graph(g, r.parts, 4)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    eng = SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                     GPHyperParams(),
                     EngineConfig(mode="stacked", use_pallas_agg=False,
                                  halo_cache=True, **halo_kw))
    return pg, model, eng


def test_halo_slot_bytes_full_range_matches_per_layer():
    """halo_slot_bytes is the refreshed-payload meter: the full slot range
    reproduces halo_bytes_per_layer, the empty range is free, and any chunk
    split partitions the payload exactly (what the CV accounting relies on)."""
    pg, _, _ = _build_halo_engine(halo_refresh_every=2)
    max_s = pg.send_idx.shape[-1]
    assert pg.halo_slot_bytes(0, max_s) == pg.halo_bytes_per_layer
    assert pg.halo_slot_bytes(0, 0) == 0
    mid = max_s // 2
    assert (pg.halo_slot_bytes(0, mid) + pg.halo_slot_bytes(mid, max_s)
            == pg.halo_bytes_per_layer)


def test_halo_cache_rejects_incompatible_configs():
    """overlap_halo hides the exchange the cache removes (pick one), and
    full-graph training must differentiate through a LIVE exchange."""
    from repro.core import partition_graph, GPHyperParams
    from repro.engine import EngineConfig, SPMDEngine
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.train.optim import AdamW

    g = make_benchmark(BENCHMARKS["tiny"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="ew", seed=0)
    pg = build_partitioned_graph(g, r.parts, 4)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=16,
                      num_classes=g.num_classes)
    mk = lambda cfg: SPMDEngine(model, model.make_loss_fn(), AdamW(lr=1e-3),
                                pg, GPHyperParams(), cfg)
    with pytest.raises(ValueError, match="overlap"):
        mk(EngineConfig(mode="stacked", use_pallas_agg=False,
                        halo_cache=True, overlap_halo=True))
    eng = mk(EngineConfig(mode="stacked", use_pallas_agg=False,
                          halo_cache=True))
    params = model.init(0)
    with pytest.raises(ValueError, match="full-graph"):
        eng.phase0_fullgraph_epoch(params, None, iters=1)
