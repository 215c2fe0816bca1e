"""Median over the traced calls of the wall of the entry point's own span
(named after the configuration's ``op``: ``cholesky``): how long the host
needs to enqueue a call's programs. ``call_s - dispatch_s`` is the time the
host only waits. None where the program's spans are not in the trace."""

import span_reduce


def read(run, name):
    return span_reduce.span_wall(run, run["config"]["op"])
