"""Share of the traced window in which no operation ran on the device
(1 - busy union / window), from the profiler trace."""


def read(run):
    idle = None if run.trace is None else run.trace.idle_share()
    return None if idle is None else 100.0 * idle
