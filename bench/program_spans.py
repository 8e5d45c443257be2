"""The loader's own spans, ``scdataset.*``, in a traced window.

The program marks its phases with ``jax.profiler.TraceAnnotation``s named
``scdataset.<phase>`` (``fetch`` > ``plan`` > ``read`` and ``assemble``;
``fetch`` > ``split``; ``to_dense``; ``put_batch``), on the same clock as
the run's ``bench.*`` spans and the device's ops.  ``tracing.Trace`` keeps
the ``bench.*`` family alone, so ``find`` reads the run's trace file again,
where the harness writes it (``bench/.trace/<cell>/``), and picks the file
whose ``bench.window`` span is the reading's window to the nanosecond.

``ProgramTrace`` is a ``tracing.Trace`` that keeps the program's spans
too.  A span's self time subtracts only the children of its own family:
the program's spans nest inside the run's, and neither changes the other's
self times.  Idle gaps of the device are named by the innermost span of
either family that the window's thread was in at the gap's middle.

Where the program records no such span (an older program), every reader
finds nothing and returns None.
"""
from __future__ import annotations

import copy
import glob
import os
from typing import Iterable, Optional

from bench import tracing

BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_DIR = os.path.join(BENCH, ".trace")
PROGRAM = "scdataset."
FETCH = "scdataset.fetch"

_FOUND: dict = {}  # the last reading's window -> its ProgramTrace


class ProgramTrace(tracing.Trace):
    """A ``tracing.Trace`` that also keeps the host spans named
    ``scdataset.*``."""

    def __init__(self, events: Iterable, planes: Optional[set] = None):
        events = list(events)
        super().__init__(events, planes)
        for plane, line, name, start, dur in events:
            if name.startswith(PROGRAM):
                start = int(start)
                self.spans.append((name, f"{plane}/{line}", start, start + int(dur)))

    @classmethod
    def from_xplane(cls, path: str, planes: Optional[set] = None) -> "ProgramTrace":
        """The host spans of both families; the device's events are left out."""
        from jax.profiler import ProfileData

        def events():
            for plane in ProfileData.from_file(path).planes:
                if not plane.name.startswith("/host:"):
                    continue
                # every Python thread's line is named alike: the index tells them apart
                for i, line in enumerate(plane.lines):
                    for e in line.events:
                        if e.name.startswith((PROGRAM, tracing.SPAN_PREFIX)):
                            yield plane.name, f"{line.name}#{i}", e.name, e.start_ns, e.duration_ns

        return cls(events(), planes)

    def self_times(self, name: str) -> list:
        """Self seconds of every ``name`` span that starts in the window: its
        duration less the parts its children of the same family cover."""
        family = PROGRAM if name.startswith(PROGRAM) else tracing.SPAN_PREFIX
        view = copy.copy(self)
        view.spans = [s for s in self.spans if s[0].startswith(family)]
        return tracing.Trace.self_times(view, name)

    def count(self, name: str) -> int:
        """``name`` spans that start in the window."""
        return sum(1 for s in self.spans if s[0] == name and self.lo <= s[2] < self.hi)

    def per_fetch_ms(self, name: str) -> Optional[float]:
        """Self ms of the ``name`` spans per ``scdataset.fetch`` span, both
        starting in the window."""
        fetches = self.count(FETCH)
        times = self.self_times(name)
        return sum(times) / fetches * 1e3 if fetches and times else None


def find(reading) -> Optional[ProgramTrace]:
    """The program's spans in ``reading``'s window: its own
    ``program_spans`` where it carries them, else read from the trace file
    under ``TRACE_DIR`` whose window is the reading's; None where there is
    no such file."""
    own = getattr(reading, "program_spans", None)
    if own is not None:
        return own
    key = (reading.trace.lo, reading.trace.hi)
    if key not in _FOUND:  # one parse serves every reader of a run
        _FOUND.clear()
        _FOUND[key] = _search(*key)
    return _FOUND[key]


def _search(lo: int, hi: int) -> Optional[ProgramTrace]:
    paths = glob.glob(os.path.join(TRACE_DIR, "*", "plugins", "profile", "*", "*.xplane.pb"))
    for path in sorted(paths, key=_mtime, reverse=True):
        try:
            found = ProgramTrace.from_xplane(path)
        except Exception:  # noqa: BLE001 -- another run's file, half written or removed
            continue
        if (found.lo, found.hi) == (lo, hi):
            return found
    return None


def _mtime(path: str) -> float:
    try:
        return os.path.getmtime(path)
    except OSError:
        return 0.0
