"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time, device time per XLA module and per op, and the idle
gaps of each device labelled by the benchmark's host span they fall in.

Planes named ``/device:TPU:<n>`` are devices: their ``XLA Modules`` line
holds one event per program run (named ``jit_<function>(<id>)``) and their
``XLA Ops`` line one event per op. Host events whose names start with
``bench.`` are the harness's own spans (``TraceAnnotation``), on the same
clock as the device events.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)

# host span -> the label an idle gap inside it gets, innermost first
GAP_LABELS = (("bench.prep", "prep"), ("bench.engine", "engine call"),
              ("bench.fetch", "result fetch"))


@dataclass
class Device:
    name: str
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    ops: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Reduced:
    devices: List[Device]
    spans: Dict[str, List[Interval]]

    def window(self) -> Interval:
        """The traced window: the harness's ``bench.window`` span."""
        (w,) = self.spans["bench.window"]
        return w

    def window_s(self) -> float:
        a, b = self.window()
        return (b - a) / 1e9

    def busy_s(self, dev: Device) -> float:
        """Seconds of the window in which some op ran on ``dev``."""
        return sum(b - a for a, b in self.busy(dev)) / 1e9

    def busy(self, dev: Device) -> List[Interval]:
        lo, hi = self.window()
        return union([(max(s, lo), min(e, hi)) for _, s, e in dev.ops
                      if e > lo and s < hi])

    def module_s(self, dev: Device, base: str) -> float:
        """Device seconds of every run of the program ``jit_<base>``."""
        return sum(e - s for n, s, e in dev.modules
                   if module_base(n) == base) / 1e9

    def calls(self) -> int:
        return len(self.spans.get("bench.call", []))

    def idle_gaps(self, dev: Device) -> List[Tuple[str, float]]:
        """Each idle stretch of ``dev`` inside the window, as (label,
        seconds), labelled by the host span around its midpoint."""
        lo, hi = self.window()
        gaps, t = [], lo
        for a, b in self.busy(dev) + [(hi, hi)]:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        return [(self.label_at((a + b) / 2), (b - a) / 1e9) for a, b in gaps]

    def label_at(self, ts: float) -> str:
        for span, label in GAP_LABELS:
            if any(a <= ts <= b for a, b in self.spans.get(span, [])):
                return label
        return "harness"


def module_base(name: str) -> str:
    """``jit__pool_jobs_ahap(1234)`` -> ``_pool_jobs_ahap``."""
    base = name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def from_events(planes) -> Reduced:
    """Build from ``[(plane_name, [(line_name, [(event_name, start_ns,
    duration_ns), ...]), ...]), ...]`` (what :func:`load` reads, and what a
    test can write by hand)."""
    devices, spans = [], {}
    for pname, lines in planes:
        if pname.startswith("/device:TPU:"):
            dev = Device(pname)
            for lname, events in lines:
                if lname == "XLA Modules":
                    dev.modules += [(n, s, s + d) for n, s, d in events]
                elif lname == "XLA Ops":
                    dev.ops += [(n, s, s + d) for n, s, d in events]
            devices.append(dev)
        elif pname.startswith("/host:"):
            for _, events in lines:
                for n, s, d in events:
                    if n.startswith("bench."):
                        spans.setdefault(n, []).append((s, s + d))
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Reduced(devices, spans)


def load(path: str) -> Reduced:
    """Read one ``.xplane.pb`` with JAX's own reader."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        if not (pl.name.startswith("/device:TPU:")
                or pl.name.startswith("/host:")):
            continue
        lines = []
        for ln in pl.lines:
            keep_all = pl.name.startswith("/device:")
            lines.append((ln.name, [
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in ln.events
                if keep_all or e.name.startswith("bench.")]))
        planes.append((pl.name, lines))
    return from_events(planes)
