"""From the profiler's trace to the numbers the per-layer readers take.

The run records host spans as ``jax.profiler.TraceAnnotation``s named
``bench.<layer>`` (``bench.window`` around the measured window), so they
share the device trace's clock.  ``Trace`` keeps only those spans and the
device's ``XLA Ops`` and ``XLA Modules`` lines, as plain events
``(plane, line, name, start_ns, dur_ns)``; everything below works on that
list, so a small recorded trace can be checked without a chip.

- busy: the union of the op intervals on each device inside the window,
  averaged over the cell's devices that ran an op;
- a span's self time: its duration less the parts its child spans (on the
  same host thread) cover;
- idle gaps: the intervals of the window in which the first device ran no
  op, each named by the innermost span the host thread was in at the gap's
  middle.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import shutil
from typing import Iterable, Optional

SPAN_PREFIX = "bench."
WINDOW = "bench.window"
OPS, MODULES = "XLA Ops", "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
TOP = 10


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def short_name(name: str) -> str:
    """``%fusion.8 = (f32[64]...) fusion(...)`` -> ``fusion.8``;
    ``jit_fig5_step(503...)`` -> ``jit_fig5_step``."""
    return name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def device_plane(device) -> str:
    """The trace's plane name of a JAX device: ``/device:TPU:0``."""
    return f"/device:{device.platform.upper()}:{device.id}"


class Trace:
    """Host spans and device events of one traced window; ``planes``, where
    given, keeps the device events of those planes alone."""

    def __init__(self, events: Iterable, planes: Optional[set] = None):
        self.spans = []  # (name, thread, start, end)
        self.ops = collections.defaultdict(list)  # device -> [(name, start, end)]
        self.modules = collections.defaultdict(list)
        for plane, line, name, start, dur in events:
            start, end = int(start), int(start) + int(dur)
            if planes is not None and _DEVICE_PLANE.match(plane) and plane not in planes:
                continue
            if name.startswith(SPAN_PREFIX):
                self.spans.append((name, f"{plane}/{line}", start, end))
            elif _DEVICE_PLANE.match(plane) and line == OPS:
                self.ops[plane].append((name, start, end))
            elif _DEVICE_PLANE.match(plane) and line == MODULES:
                self.modules[plane].append((name, start, end))
        windows = [s for s in self.spans if s[0] == WINDOW]
        if len(windows) != 1:
            raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
        _, self.thread, self.lo, self.hi = windows[0]

    @classmethod
    def from_xplane(cls, path: str, planes: Optional[set] = None) -> "Trace":
        from jax.profiler import ProfileData

        def events():
            for plane in ProfileData.from_file(path).planes:
                device = bool(_DEVICE_PLANE.match(plane.name))
                for line in plane.lines:
                    if device and line.name not in (OPS, MODULES):
                        continue
                    for e in line.events:
                        if device or e.name.startswith(SPAN_PREFIX):
                            yield plane.name, line.name, e.name, e.start_ns, e.duration_ns

        return cls(events(), planes)

    # ------------------------------------------------------------ device
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_intervals(self, device: str) -> list:
        return _union(_clip([(s, e) for _, s, e in self.ops[device]], self.lo, self.hi))

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices (0 without one)."""
        if not self.ops:
            return 0.0
        per = [sum(e - s for s, e in self.busy_intervals(d)) for d in self.ops]
        return sum(per) / len(per) / 1e9

    def module_times(self, prefix: str) -> list:
        """Device seconds of each run of the modules named ``prefix...`` in
        the window, on every device."""
        return [(e - s) / 1e9 for d in self.modules for name, s, e in self.modules[d]
                if name.startswith(prefix) and self.lo <= s < self.hi]

    # ------------------------------------------------------------- spans
    def self_times(self, name: str) -> list:
        """Self seconds of every ``name`` span that starts in the window."""
        out = []
        by_thread = collections.defaultdict(list)
        for s in self.spans:
            by_thread[s[1]].append(s)
        for spans in by_thread.values():
            spans.sort(key=lambda s: (s[2], -s[3]))
            stack = []  # [span, child time]
            for span in spans:
                while stack and stack[-1][0][3] <= span[2]:
                    self._pop(stack, name, out)
                if stack:
                    stack[-1][1] += span[3] - span[2]
                stack.append([span, 0])
            while stack:
                self._pop(stack, name, out)
        return out

    def _pop(self, stack, name, out):
        span, child = stack.pop()
        if span[0] == name and self.lo <= span[2] < self.hi:
            out.append((span[3] - span[2] - child) / 1e9)

    def span_at(self, t: int) -> str:
        """The innermost span of the window's thread that holds time ``t``."""
        best = None
        for name, thread, s, e in self.spans:
            if thread == self.thread and s <= t < e and name != WINDOW:
                if best is None or s >= best[1]:
                    best = (name, s)
        return best[0] if best else "none"

    # --------------------------------------------------------- breakdown
    def idle_gaps(self, device: Optional[str] = None) -> list:
        """(start, end) of the window's idle intervals on ``device``."""
        device = device or (sorted(self.ops)[0] if self.ops else None)
        busy = self.busy_intervals(device) if device else []
        gaps, t = [], self.lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.hi:
            gaps.append((t, self.hi))
        return gaps

    def breakdown(self) -> dict:
        """The ops that took most device time (per device, averaged) and
        the longest idle gaps, named by what the host was doing."""
        total = collections.Counter()
        for d in self.ops:
            mods = sorted(self.modules[d], key=lambda m: m[1])
            starts = [m[1] for m in mods]
            for name, s, e in self.ops[d]:
                if not self.lo <= s < self.hi:
                    continue
                i = bisect.bisect_right(starts, s) - 1
                mod = short_name(mods[i][0]) if i >= 0 and s < mods[i][2] else "?"
                total[f"{mod}/{short_name(name)}"] += (e - s) / 1e9
        n = max(1, len(self.ops))
        ops = [[k, v / n] for k, v in total.most_common(TOP)]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:TOP]
        return {"device_ops": ops,
                "idle_gaps": [[self.span_at((s + e) // 2), (e - s) / 1e9] for s, e in gaps]}


class Tracer:
    """Starts and stops the profiler around the window, into ``out_dir``."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python call tracing would slow the host several times
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def stop(self, devices=None) -> Trace:
        """The trace, with the device events of ``devices`` alone where given."""
        import jax

        jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(self.out_dir, "plugins", "profile", "*",
                                              "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"the profiler wrote no trace under {self.out_dir}")
        planes = None if devices is None else {device_plane(d) for d in devices}
        return Trace.from_xplane(paths[-1], planes)
