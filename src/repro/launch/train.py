"""End-to-end training driver (CLI).

Two modes, both exercising the paper's full pipeline (EW partitioning →
CBS sampling → GP two-phase training):

  gnn   the faithful reproduction: distributed GraphSAGE on a synthetic
        benchmark partitioned across N logical hosts
            PYTHONPATH=src python -m repro.launch.train gnn \
                --dataset products-s --parts 4 --method ew --epochs 30

  llm   the framework generalisation: any ``--arch`` from the zoo (reduced
        size on CPU) trained on an entropy-sharded domain corpus
            PYTHONPATH=src python -m repro.launch.train llm \
                --arch llama3.2-1b --shards 4 --steps 60

The gnn mode executes through the SPMD engine (repro.engine): with >= N
devices each epoch runs as one ``shard_map`` step over a partition mesh;
on a single device the SAME per-shard program runs under ``vmap`` with
identical collective semantics (DESIGN.md §3).  On a CPU host, several
devices exist only when the environment asks for them before Python
starts (``XLA_FLAGS=--xla_force_host_platform_device_count=N``).  ``--engine sequential``
selects the legible per-partition Python-loop reference, which the engine
reproduces bit-for-bit in float64 (tests/test_engine_parity.py).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def run_gnn(args) -> dict:
    from repro.pipeline import EATConfig, run_eat_distgnn

    cfg = EATConfig(
        dataset=args.dataset,
        num_parts=args.parts,
        partition_method=args.method,
        use_cbs=not args.no_cbs,
        use_gp=not args.no_gp,
        max_epochs=args.epochs,
        arch=args.arch,
        hidden_dim=args.hidden,
        batch_size=args.batch_size,
        fanouts=(args.fanout, args.fanout),
        seed=args.seed,
        centralized=args.centralized,
        engine_mode=args.engine,
        use_pallas_agg=not args.no_pallas_agg,
        overlap_halo=args.overlap_halo,
        ring_chunks=args.ring_chunks,
        async_personalize=args.async_personalize,
        async_generalize=args.async_generalize,
        double_buffer=not args.no_double_buffer,
        phase0_fraction=args.phase0_frac,
        full_graph_train=args.full_graph_train,
        full_graph_iters=args.full_graph_iters,
        halo_cache=args.halo_cache,
        halo_refresh_every=args.halo_refresh_every,
        halo_cv=args.halo_cv,
        halo_compress=args.halo_compress,
        grad_compress=args.grad_compress,
        grad_topk_frac=args.grad_topk_frac,
        grad_bucket_kb=args.grad_bucket_kb,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        keep_checkpoints=args.keep_checkpoints,
        resume=args.resume,
        feat_store=args.feat_store,
        hot_frac=args.hot_frac,
        hot_policy=args.hot_policy,
        feat_groups=args.feat_groups,
        feat_budget_mb=args.feat_budget_mb,
    )
    fault_plan = None
    if args.crash_at_epoch or args.drop_refresh_at:
        from repro.robustness import FaultPlan
        fault_plan = FaultPlan(
            crash_epochs=frozenset(args.crash_at_epoch or ()),
            drop_refresh_epochs=frozenset(args.drop_refresh_at or ()))
    result = run_eat_distgnn(cfg, verbose=True, fault_plan=fault_plan)
    print(json.dumps(result.summary(), indent=2))
    return result.summary()


def run_llm(args) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import (GPController, GPScheduleConfig, GPHyperParams,
                            make_generalize_step, make_personalize_step,
                            broadcast_to_partitions)
    from repro.data import (CorpusSpec, DomainCorpus, ShardedBatcher,
                            shard_corpus_by_entropy)
    from repro.models import Transformer
    from repro.train.optim import AdamW, apply_updates

    cfg = get_config(args.arch).reduced(d_model=args.d_model)
    model = Transformer(cfg)
    spec = CorpusSpec(num_docs=args.docs, doc_len=args.seq, vocab_size=cfg.vocab_size,
                      num_domains=8, seed=args.seed)
    corpus = DomainCorpus(spec)
    shards = shard_corpus_by_entropy(corpus, args.shards, method=args.method)
    print(f"corpus shard domain entropies ({args.method}): "
          f"{shards.shard_entropies.round(3).tolist()}")
    batcher = ShardedBatcher(corpus, shards, batch_per_shard=args.batch,
                             class_balanced=not args.no_cbs, seed=args.seed)

    def loss_fn(params, batch):
        return model.train_loss(params, batch)

    opt = AdamW(lr=3e-3, grad_clip=1.0)
    params = model.init(args.seed)
    opt_state = opt.init(params)
    gen_step = jax.jit(make_generalize_step(loss_fn, opt))
    steps_phase0 = int(args.steps * args.phase0_frac)
    hist = []
    t0 = time.time()
    for step in range(steps_phase0):
        nb = batcher.next_batch()
        # phase-0: explicit gradient averaging across shards (the pmean)
        losses, grads_acc = [], None
        for pshard in range(args.shards):
            b = {"tokens": jnp.asarray(nb["tokens"][pshard]),
                 "labels": jnp.asarray(nb["labels"][pshard])}
            l, g = jax.value_and_grad(loss_fn)(params, b)
            losses.append(float(l))
            grads_acc = g if grads_acc is None else jax.tree.map(
                lambda a, b_: a + b_, grads_acc, g)
        grads = jax.tree.map(lambda g_: g_ / args.shards, grads_acc)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        hist.append(float(np.mean(losses)))
        if step % 10 == 0:
            print(f"[phase-0] step {step:4d} loss {hist[-1]:.4f}")

    global_params = params
    # phase-1: personalization (per-shard replicas, no gradient traffic)
    pstep = jax.jit(make_personalize_step(
        loss_fn, opt, GPHyperParams(lambda_prox=args.lambda_prox)))
    pparams = broadcast_to_partitions(params, args.shards)
    popt = jax.vmap(opt.init)(pparams)
    active = jnp.ones((args.shards,), bool)
    ploss_hist = []
    for step in range(args.steps - steps_phase0):
        nb = batcher.next_batch()
        batch_p = {"tokens": jnp.asarray(nb["tokens"]),
                   "labels": jnp.asarray(nb["labels"])}
        pparams, popt, losses = pstep(pparams, popt, batch_p, global_params, active)
        ploss_hist.append(np.asarray(losses))
        if step % 10 == 0:
            print(f"[phase-1] step {step:4d} per-shard loss "
                  f"{np.asarray(losses).round(4).tolist()}")
    out = {
        "arch": args.arch, "method": args.method,
        "shard_entropies": shards.shard_entropies.tolist(),
        "phase0_final_loss": hist[-1] if hist else None,
        "phase1_final_loss": (np.asarray(ploss_hist[-1]).tolist()
                              if ploss_hist else None),
        "wall_s": time.time() - t0,
    }
    print(json.dumps(out, indent=2))
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)

    g = sub.add_parser("gnn")
    g.add_argument("--dataset", default="products-s")
    g.add_argument("--parts", type=int, default=4)
    g.add_argument("--method", default="ew",
                   choices=("random", "metis", "ew", "ew_balanced"))
    g.add_argument("--no-cbs", action="store_true")
    g.add_argument("--no-gp", action="store_true")
    g.add_argument("--epochs", type=int, default=30)
    g.add_argument("--arch", default="sage", choices=("sage", "gat"),
                   help="GraphSAGE (mean aggregator) or GAT (PPI heads "
                        "4, 4, 6; --hidden is its per-head width; needs "
                        "--full-graph-train and runs no GP phase 1)")
    g.add_argument("--hidden", type=int, default=128)
    g.add_argument("--batch-size", type=int, default=256)
    g.add_argument("--fanout", type=int, default=10)
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--engine", default="auto",
                   choices=("auto", "spmd", "stacked", "sequential"),
                   help="epoch executor: shard_map over a partition mesh, "
                        "single-device stacked vmap, or the sequential "
                        "Python-loop reference")
    g.add_argument("--no-pallas-agg", action="store_true",
                   help="use the jnp segment-op fallback instead of the "
                        "Pallas segment_agg kernel on the eval forward")
    g.add_argument("--overlap-halo", action="store_true",
                   help="boundary/interior split forward: overlap each "
                        "layer's halo exchange with interior aggregation "
                        "and restrict dense compute to owned rows")
    g.add_argument("--ring-chunks", type=int, default=0,
                   help="exchange as a ppermute ring with N chunks per "
                        "step instead of one all_to_all (0 = all_to_all); "
                        "only meaningful with --overlap-halo")
    g.add_argument("--halo-cache", action="store_true",
                   help="historical-embedding halo cache: eval forwards "
                        "aggregate against the last-received boundary "
                        "embeddings and only pay the exchange on the "
                        "--halo-refresh-every cadence (DESIGN.md §8)")
    g.add_argument("--halo-refresh-every", type=int, default=4,
                   help="full halo refresh cadence K with --halo-cache: "
                        "every K-th eval forward pays the full exchange "
                        "(1 = refresh always, i.e. no staleness)")
    g.add_argument("--halo-cv", action="store_true",
                   help="VR-GCN control-variate mode: cached forwards "
                        "refresh a rotating 1/(K-1) chunk of the send "
                        "slots instead of going fully stale between "
                        "full refreshes")
    g.add_argument("--halo-compress", default="none",
                   choices=("none", "fp16", "int8"),
                   help="quantize the eval forwards' halo exchange payload "
                        "(error-compensated per-row codec; composes with "
                        "--halo-cache and --ring-chunks, DESIGN.md §11)")
    g.add_argument("--grad-compress", default="none",
                   choices=("none", "bucketed", "topk"),
                   help="phase-0 gradient all-reduce spelling: bucketed "
                        "ring-psum slices, or top-k sparsification with "
                        "error feedback (DESIGN.md §11)")
    g.add_argument("--grad-topk-frac", type=float, default=0.01,
                   help="fraction of gradient entries --grad-compress=topk "
                        "ships per sync")
    g.add_argument("--grad-bucket-kb", type=int, default=512,
                   help="slice size of the bucketed gradient all-reduce")
    g.add_argument("--centralized", action="store_true",
                   help="single host, no partitioning (the Table IV "
                        "baseline configuration)")
    g.add_argument("--full-graph-train", action="store_true",
                   help="phase-0 trains full-graph (full-batch "
                        "value_and_grad through the distributed forward "
                        "and the differentiable Pallas aggregation op) "
                        "instead of sampled minibatches; with --centralized "
                        "this is the Table IV baseline at full-graph scale")
    g.add_argument("--full-graph-iters", type=int, default=1,
                   help="full-batch steps per phase-0 epoch with "
                        "--full-graph-train")
    g.add_argument("--async-personalize", action="store_true",
                   help="phase-1 with per-partition iteration budgets and "
                        "the CBS mini-epoch draw on device (no host NumPy "
                        "on the mini-epoch path)")
    g.add_argument("--async-generalize", action="store_true",
                   help="phase-0 epoch draw on device (uniform shuffle, or "
                        "the CBS mini-epoch with CBS on) with the train "
                        "scan and the validation eval fused into ONE "
                        "device program per epoch — retires the host "
                        "prefetcher on that path")
    g.add_argument("--no-double-buffer", action="store_true",
                   help="disable overlapping host-side sampling of epoch "
                        "t+1 with the device step of epoch t")
    g.add_argument("--checkpoint-dir", default=None,
                   help="save an epoch-granular full-pipeline checkpoint "
                        "here (atomic, checksummed, last "
                        "--keep-checkpoints retained)")
    g.add_argument("--checkpoint-every", type=int, default=1,
                   help="checkpoint every k-th epoch boundary")
    g.add_argument("--keep-checkpoints", type=int, default=3)
    g.add_argument("--resume", action="store_true",
                   help="resume from the newest intact checkpoint in "
                        "--checkpoint-dir; the finished run is bit-for-bit "
                        "the uninterrupted one")
    g.add_argument("--crash-at-epoch", type=int, nargs="*", default=None,
                   metavar="E",
                   help="fault injection: raise InjectedCrash after the "
                        "epoch-E boundary checkpoint")
    g.add_argument("--drop-refresh-at", type=int, nargs="*", default=None,
                   metavar="E",
                   help="fault injection: drop epoch E's halo-cache "
                        "refresh payload (eval serves the stale cache)")
    g.add_argument("--phase0-frac", type=float, default=None,
                   help="hard phase split: fraction of --epochs spent "
                        "generalizing (default: loss-driven trigger; "
                        "async runs default to 0.4)")
    g.add_argument("--feat-store", action="store_true",
                   help="two-tier feature store: keep the top --hot-frac "
                        "of each partition's feature rows resident on "
                        "device and stage the cold remainder from host "
                        "numpy per compiled call (DESIGN.md §12)")
    g.add_argument("--hot-frac", type=float, default=0.5,
                   help="fraction of feature rows kept device-resident "
                        "with --feat-store (0.0..1.0; 1.0 = all resident, "
                        "zero cold traffic)")
    g.add_argument("--hot-policy", default="degree",
                   choices=("degree", "freq"),
                   help="hot-set ranking: clamped in-degree, or degree "
                        "with a dominating boost for training-set rows")
    g.add_argument("--feat-groups", type=int, default=0,
                   help="stream the eval forward over groups of G <= parts "
                        "partitions (stacked mode, needs --feat-store): "
                        "only G assembled feature planes exist at once, so "
                        "graphs bigger than the stacked plane still run")
    g.add_argument("--feat-budget-mb", type=float, default=0.0,
                   help="refuse to build when peak device feature bytes "
                        "exceed this budget (0 disables) — the "
                        "bigger-than-device gate")

    l = sub.add_parser("llm")
    l.add_argument("--arch", default="llama3.2-1b")
    l.add_argument("--shards", type=int, default=4)
    l.add_argument("--method", default="ew", choices=("random", "metis", "ew"))
    l.add_argument("--no-cbs", action="store_true")
    l.add_argument("--steps", type=int, default=60)
    l.add_argument("--phase0-frac", type=float, default=0.6)
    l.add_argument("--lambda-prox", type=float, default=0.01)
    l.add_argument("--docs", type=int, default=512)
    l.add_argument("--seq", type=int, default=64)
    l.add_argument("--batch", type=int, default=8)
    l.add_argument("--d-model", type=int, default=128)
    l.add_argument("--seed", type=int, default=0)

    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "gnn":
        run_gnn(args)
    else:
        run_llm(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
