"""Share of the device's own operation time spent in collective operations
(all-reduce, all-gather, collective-permute, ...), least busy device. It
counts the whole collective, waiting for the other devices included."""

import trace_reduce


def read(run, name):
    return trace_reduce.class_share(run.get("trace"), "collective")
