"""``native_s.<kernel>``: median over the traced calls of the summed walls
of the ``stage.native.<kernel>`` spans inside one call (the C++ band chase
runs once a call, the secular solve and the deflation scan once per merge
of the divide and conquer). None where the trace holds no such span."""

import span_reduce


def read(run, name):
    return span_reduce.span_wall(run, "stage.native." + name.split(".", 1)[1])
