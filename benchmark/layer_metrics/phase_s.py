"""``phase_s.<entry>.<phase>``: median over the traced calls of the summed
wall of the library's host-phase spans ``stage.<entry>.<phase>`` inside one
call (``phase_s.triangular_solve.dispatch``: how long the host needs to
enqueue the one program of a distributed solve on every device of the
grid). The spans are unfenced: the wall of a dispatch, not of the work.
None where the trace holds no such span, as with a program that lacks it."""

import span_reduce


def read(run, name):
    return span_reduce.span_wall(run, "stage." + name.split(".", 1)[1])
