"""Share of the traced window in which no operation ran on the device, on
the device that was busy least (the worst one on a grid). From the profiler
trace: 1 - union of the device-operation intervals / window."""

import trace_reduce


def read(run, name):
    dev = trace_reduce.worst_device(run.get("trace"))
    if dev is None:
        return None
    return 100.0 * (1.0 - dev["busy_ns"] / 1e9 / run["trace"]["window_s"])
