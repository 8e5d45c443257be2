"""Consumer step on the device: device time of the step's module
(``jit_<consumer step name>`` on the trace's ``XLA Modules`` line), in ms
per run of it in the window."""


def read(r):
    times = r.trace.module_times(r.step_module)
    return sum(times) / len(times) * 1e3 if times else None
