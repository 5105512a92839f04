"""Per-layer metrics: one small reader per metric, found by name.

``metrics/<name>.py`` defines ``read(ctx) -> float | None``; ``None``
means it found nothing to read in this run, and the metric is left out of
the result line.  A share of a roofline or a peak is never reported as 0
for want of data.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass
from pathlib import Path

from . import flops
from .trace import OTHER_KERNEL, SEGMENT_AGG, Trace

__all__ = ["Context", "is_segment_agg", "load_reader", "read_all"]

METRICS = Path(__file__).resolve().parent / "metrics"


def is_segment_agg(name: str) -> bool:
    """Whether a device op is the ``segment_agg`` Pallas kernel: the trace
    tags it by its operands (``trace.short_name``)."""
    return name.endswith(SEGMENT_AGG)


@dataclass
class Context:
    """What a reader may read: the traced window, the work it held, and
    the chip's peaks."""

    trace: Trace
    dev: int                      # the fullest device (most busy time)
    chips: int
    peaks: dict                   # bf16_flops, hbm_bytes_per_s, hbm_bytes
    kind: str                     # sampled | fullgraph
    dims: tuple                   # (D, H, ..., C)
    fanouts: tuple                # sampled cells
    epochs: list                  # EpochOut of each epoch in the window
    owned: list                   # per partition: real owned nodes
    halo: list                    # per partition: halo nodes
    edges: list                   # per partition: real local edges

    @property
    def window_s(self) -> float:
        return self.trace.window_ns * 1e-9

    def segment_agg_ns(self) -> int:
        """Summed device time of the kernel's ops on the fullest device.
        Raises where the trace holds a Pallas kernel that is not
        ``segment_agg``: the kernel readers would otherwise miscount it."""
        other = sorted({n for n, _, _ in self.trace.devices.get(self.dev, ())
                        if n.endswith(OTHER_KERNEL)})
        if other:
            raise ValueError(
                f"the trace holds {len(other)} Pallas kernel op(s) that are "
                f"not segment_agg (first: {other[0]!r}); perfbench/trace.py "
                f"has to learn to tell them apart")
        return self.trace.op_ns(self.dev, is_segment_agg)

    def train_flops(self) -> float:
        """Model FLOPs of the training steps in the window (all chips)."""
        if self.kind == "sampled":
            per_seed = 3.0 * flops.sampled_seed_flops(self.dims, self.fanouts)
            return per_seed * sum(e.nodes for e in self.epochs)
        per_step = 3.0 * sum(
            flops.fullgraph_step_flops(self.dims, self.owned[p], self.edges[p])
            for p in range(len(self.owned)))
        return per_step * sum(e.steps for e in self.epochs)

    def agg_calls(self) -> list[tuple[int, int, int]]:
        """(edges, rows, width) of every aggregation the window ran: each
        epoch's evaluation forward, and in full-graph cells each training
        step's forward and transpose, over every partition (all on one
        chip)."""
        calls = []
        for p in range(len(self.owned)):
            ev = flops.eval_agg_calls(self.dims, self.owned[p], self.edges[p])
            tr = (flops.fullgraph_agg_calls(self.dims, self.owned[p],
                                            self.halo[p], self.edges[p])
                  if self.kind == "fullgraph" else [])
            for e in self.epochs:
                calls += ev + tr * e.steps
        return calls


def load_reader(name: str, root: Path = METRICS):
    path = Path(root) / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_all(entries: list[dict], ctx: Context, root: Path = METRICS) -> dict:
    """``{name: {"value", "unit"}}`` for each per-layer entry whose reader
    found something to read."""
    out = {}
    for m in entries:
        value = load_reader(m["name"], root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
