"""Share of the device's own operation time spent in matrix products (the
precision routes' slice products and the step builders' updates), least busy
device; see trace_reduce.classify for what counts as one."""

import trace_reduce


def read(run, name):
    return trace_reduce.class_share(run.get("trace"), "matmul")
