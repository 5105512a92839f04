#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as one JSON line.

    python3 perfbench/run.py --workload products-sampled --seed 7 \\
        --seconds 51 --trace 0

Set-up (timed as ``setup_s``): generate the cell's graph from its
configuration, partition it, build the program's engine, make the weights
from ``--seed`` and run the first three epochs through the window's own
calls (the first compiles, or loads from the compile cache in
``<checkout>/.jax_cache``).  Those three epochs are the ones the plain
reference follows for ``correct``.  Then the window: epochs until
``--seconds`` have passed, with no compilation allowed.  With ``--trace 1``
the window runs under the profiler and the per-layer metrics are printed
instead of the end-to-end ones.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

CHECKED_EPOCHS = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(Exception):
    pass


class CompileCounter:
    """Counts backend compilations (and compile-cache loads) while
    ``active``; registered once with JAX's monitoring."""

    def __init__(self):
        import jax

        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if self.active and event == COMPILE_EVENT:
            self.count += 1


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``, a
    fixed path, unless ``$JAX_COMPILATION_CACHE_DIR`` names one (JAX reads
    that itself); every program is cached, however quick its compile."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(jax, chips: int, peaks: dict, require_chip: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip:
        if info["platform"] != "tpu":
            raise NoChip(f"JAX found no TPU (platform {info['platform']})")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
        if info["kind"] not in peaks:
            raise NoChip(f"device kind {info['kind']!r} is not in the peaks "
                         f"table")
    return info


def peak_bytes(devs) -> int:
    """Peak device memory of the fullest chip: the arrays' peak
    (``peak_bytes_in_use``) and the peak the runtime reserved for the
    programs' temporaries (``peak_bytes_reserved``), which the first leaves
    out."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        print(f"perfbench: memory {d.id} " + " ".join(
            f"{k}={v}" for k, v in sorted(stats.items())), file=sys.stderr)
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def run_window(trainer, seconds: float):
    from jax.profiler import TraceAnnotation

    outs = []
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            with TraceAnnotation("bench.epoch"):
                outs.append(trainer.epoch())
            if time.perf_counter() >= deadline:
                break
        elapsed = time.perf_counter() - t0
    return outs, elapsed


def host_tree(tree):
    import jax
    import numpy as np

    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def checked_epochs(trainer):
    """The first epochs, through the window's own call: the set-up's
    warm-up, and the steps the reference follows."""
    from_program_params = trainer.cell.spec.model.from_program_params
    outs = [trainer.epoch()]
    mu1 = from_program_params(trainer.opt_state.mu)
    outs += [trainer.epoch() for _ in range(CHECKED_EPOCHS - 1)]
    prog = {"losses": [o.losses for o in outs], "mu1": mu1,
            "params": from_program_params(trainer.params),
            "val_preds": [o.val_preds for o in outs]}
    spec = [o.batch_ids if trainer.cell.kind == "sampled" else o.steps
            for o in outs]
    return prog, spec


def reference_numbers(cell, layers0, prog, spec, dtype=None):
    """Run the plain reference over the checked epochs and compare."""
    import jax.numpy as jnp

    from perfbench import check
    from perfbench.reference import Reference

    ref = Reference(cell.spec.config, cell.spec.model, cell.graph, cell.parts,
                    dtype=dtype or jnp.float32)
    out = ref.run(layers0, spec, eval_rows=cell.val_ids)
    return check.compare(prog, out, layers0, float(cell.spec.config["adam_b1"]))


def placement(cell) -> dict[int, list[int]]:
    """Device id -> the partitions it holds: all on the one chip, or
    partition p on the cell's p-th chip."""
    if len(cell.devices) == 1:
        return {cell.devices[0].id: list(range(cell.num_parts))}
    return {d.id: [p] for p, d in enumerate(cell.devices)}


def layer_context(cell, spec, epochs, tr, info, peaks):
    from perfbench.readers import Context

    pg = cell.pg
    edges = [int((pg.edge_mask[p] > 0).sum()) for p in range(pg.num_parts)]
    used = placement(cell)
    return Context(
        trace=tr, dev=tr.fullest_device(among=used), chips=spec.chips,
        peaks=peaks.get(info["kind"], {}), kind=cell.kind,
        dims=spec.model.layer_dims(spec.config),
        fanouts=tuple(spec.traffic.get("fanouts", ())), epochs=epochs,
        owned=[int(x) for x in pg.n_own], halo=[int(x) for x in pg.n_halo],
        edges=edges, model=spec.model, placement=used)


def main(argv=None, *, require_chip: bool = True, faults=(),
         root: Path = ROOT, compile_cache: bool = True) -> int:
    """The benchmark run.  Tests call it with ``require_chip=False`` (and a
    fault planted in the timed path) on a CPU at a tiny size, with
    ``compile_cache=False`` so they leave the process's JAX settings as
    they were."""
    args = parse(argv)
    import jax

    if compile_cache:
        enable_compile_cache(root)
    from perfbench import check, harness, readers
    from perfbench import trace as tracing

    bench = harness.load_json(Path(root) / "BENCHMARK.json")
    spec = harness.load_cell(args.workload, root)
    peaks = harness.load_json(Path(root) / "perfbench" / "peaks.json")
    try:
        info = device_info(jax, spec.chips, peaks, require_chip)
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    counter = CompileCounter()

    cell = harness.Cell(spec, faults=faults)
    trainer = harness.Trainer(cell, args.seed, record_epochs=CHECKED_EPOCHS)
    layers0 = host_tree(trainer.params0)
    prog, ref_spec = checked_epochs(trainer)
    setup_s = time.perf_counter() - T_START
    print(f"perfbench: {args.workload} set-up {setup_s:.3f}s "
          f"({', '.join(f'{k} {v:.3f}' for k, v in cell.timings.items())}) "
          f"engine={cell.engine.mode} {cell.pg.summary()}", file=sys.stderr)

    counter.active = True
    if args.trace:
        (epochs, elapsed), tr = tracing.capture(
            lambda: run_window(trainer, args.seconds))
    else:
        epochs, elapsed = run_window(trainer, args.seconds)
        tr = None
    counter.active = False
    info["memory_peak_bytes"] = peak_bytes(cell.devices)

    nodes = sum(e.nodes for e in epochs)
    attempted = len(epochs)
    failed = sum(1 for e in epochs if not math.isfinite(float(e.losses.mean())))
    breakdown = None
    if args.trace:
        ctx = layer_context(cell, spec, epochs, tr, info, peaks)
        entries = [m for m in bench["per_layer"]
                   if args.workload in m.get("workloads", [args.workload])]
        metrics = readers.read_all(entries, ctx)
        busy = [tr.busy_ns(d.id) for d in cell.devices]
        info["busy_s"] = sum(busy) / len(busy) * 1e-9
        info["window_s"] = tr.window_ns * 1e-9
        if ctx.dev is not None:
            breakdown = {"device_ops": tr.top_ops(ctx.dev),
                         "idle_gaps": tr.idle_gaps(ctx.dev)}
    else:
        values = {"train_nodes_per_s": nodes / elapsed,
                  "peak_hbm_gib": info["memory_peak_bytes"] / 2 ** 30,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if args.workload in m.get("workloads", [args.workload])}

    # the program's state goes before the reference runs on the chip
    trainer.close()
    trainer.params = trainer.opt_state = None
    cell.engine = None
    del epochs
    gc.collect()

    numbers = reference_numbers(cell, layers0, prog, ref_spec)
    verdict = check.judge(numbers, check.load_limits(args.workload))
    checks = verdict["checks"]
    checks["window_compiles"] = [counter.count, 0]
    correct = bool(verdict["ok"] and counter.count == 0 and failed == 0)
    cell.close()

    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, (value, limit) in checks.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
