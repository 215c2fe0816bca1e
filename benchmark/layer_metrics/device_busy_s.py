"""Seconds a call keeps the device busy: the union of the device-operation
intervals in the traced window (least busy device), over the calls traced."""

import trace_reduce


def read(run, name):
    dev = trace_reduce.worst_device(run.get("trace"))
    if dev is None or not run.get("traced_calls"):
        return None
    return dev["busy_ns"] / 1e9 / run["traced_calls"]
