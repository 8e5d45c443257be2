"""Consumer step, across chips: device time of the collective ops that run
inside the step's module, in ms per step, averaged over the cell's chips.

A collective is an op whose name starts with ``all-reduce``,
``reduce-scatter``, ``all-gather``, ``all-to-all`` or ``collective-permute``
(their ``-start`` and ``-done`` halves included).  On a TPU v5e the compiler
combines the data-parallel Fig. 5 step's gradient sums, and the loss's,
into one ``all-reduce.<n>``.  None where no step ran a collective: a cell
on one chip has none."""
import bisect
import re

from bench import tracing

COLLECTIVE = re.compile(r"^(all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute)")


def per_chip_ms(trace, module: str) -> list:
    """For each device that ran ``module`` in the window: the ms of
    collective ops inside those runs, per run."""
    out = []
    for device, modules in trace.modules.items():
        runs = sorted((s, e) for name, s, e in modules
                      if name.startswith(module) and trace.lo <= s < trace.hi)
        if not runs:
            continue
        starts = [s for s, _ in runs]
        ns = 0
        for name, s, e in trace.ops[device]:
            if not COLLECTIVE.match(tracing.short_name(name)):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                ns += e - s
        out.append(ns / len(runs) / 1e6)
    return out


def read(r):
    per = per_chip_ms(r.trace, r.step_module)
    return sum(per) / len(per) if any(per) else None
