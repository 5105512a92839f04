"""Per-layer metrics: one small reader per metric, found by name.

``metrics/<name>.py`` defines ``read(ctx) -> float | None``; ``None``
means it found nothing to read in this run, and the metric is left out of
the result line.  A share of a roofline or a peak is never reported as 0
for want of data.
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path
from types import ModuleType

from . import byname
from .trace import KERNELS, OTHER_KERNEL, Trace, tagged

__all__ = ["Context", "load_reader", "read_all"]

METRICS = Path(__file__).resolve().parent / "metrics"


@dataclass
class Context:
    """What a reader may read: the traced window, the work it held, and
    the chip's peaks."""

    trace: Trace
    dev: int                      # the fullest device (most busy time)
    chips: int
    peaks: dict                   # bf16_flops, hbm_bytes_per_s, hbm_bytes
    kind: str                     # sampled | fullgraph
    dims: tuple                   # (D, H, ..., C): the model's layer_dims
    fanouts: tuple                # sampled cells
    epochs: list                  # EpochOut of each epoch in the window
    owned: list                   # per partition: real owned nodes
    halo: list                    # per partition: halo nodes
    edges: list                   # per partition: real local edges
    model: ModuleType | None = None   # the cell's model module
    # device id -> the partitions it holds; None: all on one chip
    placement: dict | None = None

    @property
    def window_s(self) -> float:
        return self.trace.window_ns * 1e-9

    def parts_on_dev(self) -> list[int]:
        """The partitions whose work runs on ``dev``."""
        if self.placement is None:
            return list(range(len(self.owned)))
        return list(self.placement.get(self.dev, ()))

    def kernel(self, name: str, root: Path = KERNELS):
        """The kernel file ``kernels/<name>.py``."""
        return byname.load("kernel", name, root)

    def kernel_ns(self, name: str) -> int:
        """Summed device time of the ops the trace tagged ``[<name>]`` on
        the fullest device.  Raises where the trace holds a Pallas op that
        no kernel file claims: the kernel readers would otherwise miscount
        it."""
        other = sorted({n for n, _, _ in self.trace.devices.get(self.dev, ())
                        if n.endswith(OTHER_KERNEL)})
        if other:
            raise ValueError(
                f"the trace holds {len(other)} Pallas kernel op(s) that no "
                f"file of perfbench/kernels claims (first: {other[0]!r}); "
                f"add the kernel's file")
        return self.trace.op_ns(self.dev, lambda n: tagged(n, name))

    def train_flops(self) -> float:
        """Model FLOPs of the training steps in the window (all chips),
        as the model module counts them."""
        return self.model.train_flops(self)


def load_reader(name: str, root: Path = METRICS):
    return byname.load("metric", name, root).read


def read_all(entries: list[dict], ctx: Context, root: Path = METRICS) -> dict:
    """``{name: {"value", "unit"}}`` for each per-layer entry whose reader
    found something to read."""
    out = {}
    for m in entries:
        value = load_reader(m["name"], root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
