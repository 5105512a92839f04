#!/usr/bin/env python3
"""Bring-up smoke of GraphSAGE EAT training on a TPU, in one process.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the shard_map engine on a 2x2 host

One chip runs the repo's default training job (products-s, P=4 EW
partitions, hidden 128, fanout 10, batch 256, 2-layer GraphSAGE, CBS and
GP on; ``auto`` resolves to the stacked engine, all partitions vmapped):

  1. ``segment_mean_op`` forward and ``jax.grad`` at the job's real block
     shapes, compiled (not interpreted), against ``kernels/ref.py``;
  2. ``run_eat_distgnn`` for a few epochs with both GP phases;
  3. one ``full_graph_train`` epoch (value_and_grad through the kernel).

``--four-chips`` runs only the same job on the ``spmd`` engine (one
partition per chip) and the stacked engine on one of those chips, at the
default and at float32 matmul precision; it compares per-epoch validation
micro-F1 at both, the final parameters at float32, and checks that each
chip holds only its own partition's shards.

Every check that fails exits non-zero.  A platform other than ``tpu`` is a
failure: the script never falls back to the CPU.  The last line of stdout
is one JSON object naming the device JAX reports.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

EPOCHS = 20             # phase0_fraction 0.5: 10 generalization + 10 GP
PARAMS_TOL = 1e-5       # spmd vs stacked final params (max abs diff, at
                        # float32 matmul precision)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def job_config(**kw):
    from repro.pipeline import EATConfig

    base = dict(dataset="products-s", num_parts=4, partition_method="ew",
                hidden_dim=128, fanouts=(10, 10), batch_size=256,
                use_cbs=True, use_gp=True, seed=0)
    base.update(kw)
    return EATConfig(**base)


def majority_rate(graph) -> float:
    """Test micro-F1 of always predicting the training split's majority
    class."""
    import numpy as np

    top = np.bincount(graph.labels[graph.train_idx]).argmax()
    return float((graph.labels[graph.test_idx] == top).mean())


def kernel_phase(graph) -> None:
    """Forward and grad of the aggregation op at the job's largest
    partition's block shapes, compiled, against the jnp oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import partition_graph
    from repro.graph import build_partitioned_graph
    from repro.kernels import ref
    from repro.kernels import segment_agg as sa

    print("[kernel] segment_mean_op at the default job's block shapes",
          flush=True)
    r = partition_graph(graph.indptr, graph.indices, graph.features,
                        graph.labels, 4, method="ew", seed=0, fanout_k=10)
    pg = build_partitioned_graph(graph, r.parts, 4)
    p = int(np.argmax((pg.edge_mask > 0).sum(axis=1)))
    real = pg.edge_mask[p] > 0
    src, dst = pg.edge_src[p][real], pg.edge_dst[p][real]
    n = pg.max_nodes
    blocks = {k: jnp.asarray(v)
              for k, v in sa.build_vjp_blocks(src, dst, n, n).items()}
    print(f"  partition {p}: {int(real.sum())} edges, "
          f"{blocks['blk'].shape[0]} fwd chunks, "
          f"{blocks['t_blk'].shape[0]} bwd chunks, {n} rows", flush=True)
    rng = np.random.default_rng(0)
    srcj, dstj = jnp.asarray(src), jnp.asarray(dst)
    for d in (graph.feature_dim, 128):
        x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
        before = sa.pallas_call_count()
        t0 = time.perf_counter()
        # everything enters as an argument: closed-over arrays would become
        # HLO constants for XLA to fold
        fwd_l = jax.jit(lambda x, b: sa.segment_mean_op(
            x, b, num_rows=n)).lower(x, blocks)
        grad_l = jax.jit(jax.grad(lambda x, w, b: (sa.segment_mean_op(
            x, b, num_rows=n) * w).sum())).lower(x, w, blocks)
        fwd, grad = fwd_l.compile(), grad_l.compile()
        t_compile = time.perf_counter() - t0
        staged = sa.pallas_call_count() - before
        out = jax.block_until_ready(fwd(x, blocks))
        t0 = time.perf_counter()
        out = jax.block_until_ready(fwd(x, blocks))
        t_fwd = time.perf_counter() - t0
        gx = jax.block_until_ready(grad(x, w, blocks))
        t0 = time.perf_counter()
        gx = jax.block_until_ready(grad(x, w, blocks))
        t_grad = time.perf_counter() - t0
        want = ref.segment_agg_ref(x, srcj, dstj, n)
        g_want = jax.grad(lambda x: (ref.segment_agg_ref(
            x, srcj, dstj, n) * w).sum())(x)
        err = float(jnp.abs(out - want).max())
        g_err = float(jnp.abs(gx - g_want).max())
        print(f"  d={d}: compile {t_compile:.2f}s, fwd {t_fwd * 1e3:.3f}ms, "
              f"grad {t_grad * 1e3:.3f}ms, max|fwd-ref| {err:.3e}, "
              f"max|grad-ref| {g_err:.3e}", flush=True)
        check(staged >= 2, f"d={d}: fwd and bwd kernels staged ({staged})")
        # an interpreted kernel lowers to plain XLA ops, a compiled one to
        # a Mosaic custom call (identical kernels may share one lowering)
        check("tpu_custom_call" in fwd_l.as_text()
              and "tpu_custom_call" in grad_l.as_text(),
              f"d={d}: Mosaic kernel lowered in the fwd and grad programs")
        check(bool(jnp.isfinite(out).all()) and out.shape == (n, d),
              f"d={d}: forward finite, shape {(n, d)}")
        check(np.allclose(np.asarray(out), np.asarray(want),
                          rtol=1e-5, atol=1e-5),
              f"d={d}: forward matches the jnp oracle (f32 tolerance)")
        check(np.allclose(np.asarray(gx), np.asarray(g_want),
                          rtol=1e-5, atol=1e-5),
              f"d={d}: grad matches the jnp oracle (f32 tolerance)")


def report_run(tag: str, res) -> None:
    dev = ", ".join(f"{t:.4f}" for t in res.epoch_device_s)
    print(f"  {tag}: engine {res.engine_mode}, epochs {res.epochs_run} "
          f"(GP from epoch {res.personalize_start_epoch}, "
          f"{res.phase1_epochs} phase-1), per-epoch device s [{dev}], "
          f"test micro-F1 {res.f1.micro:.4f}", flush=True)


def training_phase(graph) -> None:
    import numpy as np

    from repro.kernels import segment_agg as sa
    from repro.pipeline import run_eat_distgnn

    base_rate = majority_rate(graph)
    print(f"[train] default job, {EPOCHS} epochs, GP at half; majority-class "
          f"rate on test {base_rate:.4f}", flush=True)
    before = sa.pallas_call_count()
    t0 = time.perf_counter()
    res = run_eat_distgnn(job_config(max_epochs=EPOCHS, phase0_fraction=0.5),
                          verbose=True)
    wall = time.perf_counter() - t0
    report_run(f"wall {wall:.1f}s", res)
    check(sa.pallas_call_count() > before, "segment_agg staged by the run")
    check(res.engine_mode == "stacked", "auto resolved to the stacked engine")
    check(res.personalize_start_epoch > 0 and res.phase1_epochs > 0,
          "both GP phases ran")
    check(res.loss_history[EPOCHS // 2 - 1] < res.loss_history[0]
          and res.val_history[EPOCHS // 2 - 1] > res.val_history[0],
          f"phase-0 loss fell ({res.loss_history[0]:.4f} -> "
          f"{res.loss_history[EPOCHS // 2 - 1]:.4f}) and val micro-F1 rose "
          f"({res.val_history[0]:.4f} -> "
          f"{res.val_history[EPOCHS // 2 - 1]:.4f})")
    # products-s's OOD split gives train and val nodes one class only, so no
    # model beats the majority-class rate on test reliably; the learning
    # check is the loss/val pair above, and test F1 must beat chance
    chance = 1.0 / graph.num_classes
    print(f"  test micro-F1 {res.f1.micro:.4f} vs majority-class rate "
          f"{base_rate:.4f} (train classes: "
          f"{len(np.unique(graph.labels[graph.train_idx]))})", flush=True)
    check(bool(np.isfinite(res.f1.micro)) and res.f1.micro > chance,
          f"test micro-F1 {res.f1.micro:.4f} finite and above chance "
          f"{chance:.4f}")

    print("[train] one full_graph_train epoch", flush=True)
    before = sa.pallas_call_count()
    t0 = time.perf_counter()
    res = run_eat_distgnn(job_config(max_epochs=1, full_graph_train=True),
                          verbose=True)
    wall = time.perf_counter() - t0
    report_run(f"wall {wall:.1f}s", res)
    staged = sa.pallas_call_count() - before
    # 2 layers x (fwd + transpose bwd) in the train trace, + eval forwards
    check(staged >= 5, f"fwd and bwd kernels staged in full-graph training "
                       f"({staged})")
    check(bool(np.isfinite(res.loss_history).all()),
          "full-graph loss finite")
    check(bool(np.isfinite(res.f1.micro)), "full-graph test micro-F1 finite")


def four_chip_phase() -> None:
    import jax
    import numpy as np

    from repro.core import GPHyperParams, partition_graph
    from repro.engine import EngineConfig, make_engine
    from repro.graph import (BENCHMARKS, GraphSAGE, build_partitioned_graph,
                             make_benchmark)
    from repro.pipeline import run_eat_distgnn
    from repro.train.optim import AdamW

    print("[four-chips] engine placement: one partition per chip", flush=True)
    g = make_benchmark(BENCHMARKS["products-s"])
    r = partition_graph(g.indptr, g.indices, g.features, g.labels, 4,
                        method="ew", seed=0, fanout_k=10)
    pg = build_partitioned_graph(g, r.parts, 4)
    model = GraphSAGE(feature_dim=g.feature_dim, hidden_dim=128,
                      num_classes=g.num_classes)
    eng = make_engine(model, model.make_loss_fn(), AdamW(lr=1e-3), pg,
                      hp=GPHyperParams(), config=EngineConfig(mode="spmd"))
    devices = list(eng._mesh.devices.flat)
    leaves = jax.tree_util.tree_leaves((eng.shards, eng.labels, eng.masks))
    own = True
    for leaf in leaves:
        for sh in leaf.addressable_shards:
            p = devices.index(sh.device)
            own &= (sh.index[0] == slice(p, p + 1)
                    and sh.data.shape[0] == 1)
        own &= len(leaf.addressable_shards) == 4
    check(own, f"each of the 4 chips holds only its own partition's slice "
               f"of all {len(leaves)} resident arrays")
    del eng

    # At the default precision the TPU rounds f32 dot operands to bf16, so
    # one f32 ulp between the two engines' programs (a fusion or reduction
    # order XLA picks per program) can move an operand by a bf16 ulp, and
    # training amplifies it.  The params bound is checked at float32
    # precision, where only the engines' own arithmetic differs.
    for precision in ("default", "float32"):
        print(f"[four-chips] default job, {EPOCHS} epochs, matmul precision "
              f"{precision}: spmd vs stacked", flush=True)
        runs = {}
        with jax.default_matmul_precision(precision):
            for mode in ("spmd", "stacked"):
                t0 = time.perf_counter()
                res = run_eat_distgnn(job_config(max_epochs=EPOCHS,
                                                 phase0_fraction=0.5,
                                                 engine_mode=mode),
                                      verbose=True)
                report_run(f"{mode} wall {time.perf_counter() - t0:.1f}s",
                           res)
                runs[mode] = res
        a, b = runs["spmd"], runs["stacked"]
        check(a.engine_mode == "spmd" and b.engine_mode == "stacked",
              "engines resolved as asked")
        print(f"  val micro-F1 per epoch: spmd {a.val_history}", flush=True)
        print(f"  val micro-F1 per epoch: stacked {b.val_history}",
              flush=True)
        diff = max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
                   for x, y in zip(jax.tree_util.tree_leaves(a.final_params),
                                   jax.tree_util.tree_leaves(b.final_params)))
        print(f"  final params max|spmd - stacked| {diff:.3e} ({precision})",
              flush=True)
        check(a.val_history == b.val_history,
              f"per-epoch val micro-F1 equal ({precision})")
        if precision == "float32":
            check(diff <= PARAMS_TOL, f"final params within {PARAMS_TOL:g}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the spmd-vs-stacked comparison on a "
                         "4-chip host")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)

    import jax

    from repro.kernels import segment_agg as sa
    from repro.launch.cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    devices = jax.devices()
    for d in devices:
        print(f"device {d.id}: platform={d.platform} kind={d.device_kind}",
              flush=True)
    dev0 = devices[0]
    want = 4 if args.four_chips else 1
    try:
        check(dev0.platform == "tpu", f"platform is tpu ({dev0.platform})")
        check(len(devices) >= want, f"at least {want} device(s) "
                                    f"({len(devices)})")
        check(not sa.default_interpret(), "Pallas runs compiled, not "
                                          "interpreted")
        if args.four_chips:
            four_chip_phase()
        else:
            from repro.graph import BENCHMARKS, make_benchmark

            graph = make_benchmark(BENCHMARKS["products-s"])
            kernel_phase(graph)
            training_phase(graph)
        check(sa.interpreted_call_count() == 0,
              "no kernel was staged in interpret mode")
        stats = dev0.memory_stats() or {}
        peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
        print(f"  device 0 peak bytes in use {peak} of {limit}", flush=True)
        check(peak is not None and limit is not None and peak < limit,
              "peak device bytes under the chip's HBM limit")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
