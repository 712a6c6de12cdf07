"""Reduce a profiler trace of the measured window to the per-layer numbers.

The harness traces the window with ``jax.profiler`` and marks it, and each
request, with host spans of its own (``bench:window``, ``bench:request``,
...).  :func:`load` reads the ``.xplane.pb`` into plain interval lists;
everything else here works on those lists, so the arithmetic is checked
on a synthetic trace by the benchmark's tests.

* busy time of a device -- the union of its operations' intervals inside
  the window; its idle share is one minus busy over the window;
* module launches -- the device's ``XLA Modules`` events whose name
  matches, with their durations and the idle gaps between consecutive ones;
* breakdown -- the operations that took the most device time, and the
  longest device-idle gaps named by the innermost benchmark span that was
  open on the host in the middle of each.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def merge(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_length(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(intervals, lo, hi))


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e in merge(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


@dataclass
class Device:
    ops: list = field(default_factory=list)       # (name, start, end) ns
    modules: list = field(default_factory=list)   # (name, start, end) ns


@dataclass
class TraceSummary:
    window: tuple[float, float]                   # ns
    devices: dict                                 # id -> Device
    spans: list = field(default_factory=list)     # host (name, start, end)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def _busy_intervals(self, dev: Device):
        evs = dev.ops or dev.modules
        return [(s, e) for _, s, e in evs]

    def busy_ns(self, dev: Device) -> float:
        return union_length(self._busy_intervals(dev), *self.window)

    def mean_busy_ns(self) -> float:
        if not self.devices:
            return 0.0
        return (sum(self.busy_ns(d) for d in self.devices.values())
                / len(self.devices))

    def idle_percent(self) -> float | None:
        if not self.devices or self.window_ns <= 0:
            return None
        return 100.0 * (1.0 - self.mean_busy_ns() / self.window_ns)

    def launches(self, pattern: str) -> dict:
        """Per device, the window's module events whose name matches
        ``pattern`` (a regular expression), in start order."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return {k: sorted((ev for ev in d.modules
                           if rx.search(ev[0]) and ev[1] >= lo
                           and ev[2] <= hi), key=lambda ev: ev[1])
                for k, d in self.devices.items()}

    def span_at(self, t: float) -> str:
        """Innermost benchmark span open at host time ``t``."""
        best = None
        for name, s, e in self.spans:
            if s <= t <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        return best[0][len(SPAN_PREFIX):] if best else "outside spans"

    def breakdown(self, top: int = 10) -> dict:
        lo, hi = self.window
        n = max(1, len(self.devices))
        per_op: dict[str, float] = {}
        for d in self.devices.values():
            for name, s, e in d.ops or d.modules:
                dur = min(e, hi) - max(s, lo)
                if dur > 0:
                    per_op[name] = per_op.get(name, 0.0) + dur
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        idle = []
        if self.devices:
            first = self.devices[min(self.devices)]
            gs = sorted(gaps(self._busy_intervals(first), lo, hi),
                        key=lambda g: g[0] - g[1])[:top]
            idle = [[self.span_at((s + e) / 2), (e - s) / 1e9]
                    for s, e in gs]
        return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
                "idle_gaps": idle}


def summarize(spans, devices: dict) -> TraceSummary:
    """A summary over the benchmark's window span, or over every event
    when the window span is missing."""
    win = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if win:
        window = (win[-1][0], win[-1][1])
    else:
        pts = [t for d in devices.values() for _, s, e in d.ops + d.modules
               for t in (s, e)]
        window = (min(pts), max(pts)) if pts else (0.0, 0.0)
    return TraceSummary(window=window, devices=devices,
                        spans=[sp for sp in spans
                               if sp[0].startswith(SPAN_PREFIX)])


def find_xplane(logdir: Path) -> Path | None:
    found = sorted(Path(logdir).glob("**/*.xplane.pb"))
    return found[-1] if found else None


def load(path: Path) -> TraceSummary:
    """Read one ``.xplane.pb`` into a :class:`TraceSummary`."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    spans, devices = [], {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    # an op's event name is its HLO text; keep "%name"
                    dev.ops.extend((e.name.split(" = ", 1)[0], e.start_ns,
                                    e.end_ns) for e in line.events)
                elif line.name == MODULES_LINE:
                    dev.modules.extend((e.name, e.start_ns, e.end_ns)
                                       for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return summarize(spans, devices)
