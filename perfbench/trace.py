"""Profiler trace of a window, and its reduction to per-layer numbers.

``capture`` records the JAX profiler around a callable and reads the
``.xplane.pb`` it wrote into a :class:`Trace`: per device the operations
that ran on it, and the host's events (the harness's own ``bench.*``
spans among them), all on one clock in nanoseconds.  The reductions work
on that plain form, so a hand-built trace tests them.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field
from pathlib import Path

from . import byname

__all__ = ["KERNELS", "OTHER_KERNEL", "Trace", "capture", "from_xspace",
           "kernel_tag", "load_kernels", "short_name", "tagged", "union_ns"]

WINDOW_SPAN = "bench.window"
# the kernel files: one per Pallas kernel the benchmark knows
KERNELS = Path(__file__).resolve().parent / "kernels"
# the tag short_name gives a Pallas op that no kernel file claims
OTHER_KERNEL = " [pallas]"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
# the line of a device plane that holds one event per operation executed
_OP_LINES = ("XLA Ops",)


@dataclass
class Trace:
    window: tuple[int, int]                       # ns, from the window span
    devices: dict[int, list[tuple[str, int, int]]] = field(default_factory=dict)
    host: list[tuple[str, int, int]] = field(default_factory=list)

    @property
    def window_ns(self) -> int:
        return max(0, self.window[1] - self.window[0])

    def _clipped(self, dev: int, match=None):
        lo, hi = self.window
        for name, s, e in self.devices.get(dev, ()):
            if match is not None and not match(name):
                continue
            s, e = max(s, lo), min(e, hi)
            if e > s:
                yield name, s, e

    def busy_ns(self, dev: int) -> int:
        return union_ns([(s, e) for _, s, e in self._clipped(dev)])

    def op_ns(self, dev: int, match) -> int:
        """Summed time of the operations whose name ``match`` accepts."""
        return sum(e - s for _, s, e in self._clipped(dev, match))

    def fullest_device(self, among=None) -> int | None:
        """The device with the most busy time, of ``among`` (device ids)
        where given."""
        devs = sorted(d for d in self.devices
                      if among is None or d in among)
        return max(devs, key=self.busy_ns) if devs else None

    def top_ops(self, dev: int, top: int = 10) -> list[list]:
        total: dict[str, int] = {}
        for name, s, e in self._clipped(dev):
            total[name] = total.get(name, 0) + (e - s)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns * 1e-9] for name, ns in ranked]

    def idle_gaps(self, dev: int, top: int = 10) -> list[list]:
        """The longest stretches of the window with no operation on
        ``dev``, each named by what the host was doing in it: the shortest
        host event that covers at least half the gap, else the one that
        overlaps it most, else ``python`` (no traced host event)."""
        lo, hi = self.window
        spans = sorted((s, e) for _, s, e in self._clipped(dev))
        gaps, t = [], lo
        for s, e in spans:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        return [[self._host_label(s, e), (e - s) * 1e-9] for s, e in gaps]

    def _host_label(self, s: int, e: int) -> str:
        best_cover, best_overlap = None, None
        for name, hs, he in self.host:
            if name == WINDOW_SPAN:
                continue
            ov = min(e, he) - max(s, hs)
            if ov <= 0:
                continue
            if 2 * ov >= (e - s) and (best_cover is None
                                      or he - hs < best_cover[1]):
                best_cover = (name, he - hs)
            if best_overlap is None or ov > best_overlap[1]:
                best_overlap = (name, ov)
        if best_cover is not None:
            return best_cover[0]
        return best_overlap[0] if best_overlap is not None else "python"


def union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def from_xspace(profile) -> Trace:
    """A :class:`Trace` from ``jax.profiler.ProfileData``: the device
    planes' per-op lines (each op named by :func:`short_name` and the
    program it ran in), every host event, and the window span."""
    devices: dict[int, list] = {}
    host: list = []
    window = None
    kernels = load_kernels()
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 _module_name(ev.name))
                for ev in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ()))
            ops = devices.setdefault(int(m.group(2)), [])
            for name in _OP_LINES:
                for ev in (lines[name].events if name in lines else ()):
                    s = int(ev.start_ns)
                    ops.append((_in_module(modules, s)
                                + short_name(ev.name, kernels),
                                s, int(ev.start_ns + ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                    host.append((ev.name, s, e))
                    if ev.name == WINDOW_SPAN:
                        window = (s, e)
    if window is None:
        raise ValueError(f"trace holds no {WINDOW_SPAN} span")
    return Trace(window=window, devices=devices, host=host)


def tagged(op: str, kernel: str) -> bool:
    """Whether a traced op's name carries the tag of ``kernel``."""
    return op.endswith(f" [{kernel}]")


def load_kernels(root: Path = KERNELS) -> dict:
    """``{name: module}`` of the kernel files, in the order they claim an
    op."""
    return byname.load_all("kernel", root)


def short_name(text: str, kernels: dict | None = None) -> str:
    """``%fusion.3 fusion f32[6614528,256]`` from an op's HLO text: the
    instruction, its opcode and its result shape without layouts.  A
    Pallas kernel's op is tagged ``[<name>]`` by the first kernel file
    (``kernels``, else all of them) whose ``matches`` accepts its HLO text,
    ``[pallas]`` where none does."""
    if " = " not in text:
        return text
    instr, rest = text.split(" = ", 1)
    rest = _LAYOUT.sub("", rest)
    m = _OPCODE.search(rest)
    if m is None:
        return instr
    shape = rest[:m.start()].strip()
    if len(shape) > 60:
        shape = shape[:57] + "..."
    return f"{instr} {m.group(1)} {shape}{kernel_tag(instr, rest, kernels)}"


def kernel_tag(instr: str, rest: str, kernels: dict | None = None) -> str:
    """The tag of a Pallas kernel's op, from its instruction name and its
    HLO text without layouts; ``""`` for any other op.  On a TPU the
    kernel is a ``tpu_custom_call`` custom call, or, where XLA fuses it, a
    ``kCustom`` fusion that keeps the ``%closed_call`` name of the vmapped
    ``pallas_call``; the op's metadata does not name the kernel."""
    pallas = ("tpu_custom_call" in rest
              or (instr.startswith("%closed_call") and "kind=kCustom" in rest))
    if not pallas:
        return ""
    for name, mod in (load_kernels() if kernels is None
                      else kernels).items():
        if mod.matches(rest):
            return f" [{name}]"
    return OTHER_KERNEL


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][\w\-]*)\(")


def _module_name(name: str) -> str:
    """``jit_with_resident(13308775881484029719)`` -> ``with_resident#029719``."""
    m = re.match(r"^(?:jit_)?(.*?)\((\d+)\)$", name)
    return f"{m.group(1)}#{m.group(2)[-6:]}" if m else name


def _in_module(modules, t: int) -> str:
    """``<program>/`` for the program running at ``t`` (modules sorted)."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][0] <= t < modules[lo - 1][1]:
        return modules[lo - 1][2] + "/"
    return ""


def capture(fn):
    """Run ``fn()`` under the JAX profiler; returns ``(fn's result,
    Trace)``.  ``fn`` must open a ``bench.window`` span around the part
    the reductions cover.  The trace files are deleted once read."""
    import jax
    from jax.profiler import ProfileData

    logdir = tempfile.mkdtemp(prefix="perfbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # host spans and runtime events only
    opts.host_tracer_level = 2
    try:
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        return out, from_xspace(ProfileData.from_file(paths[0]))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
