"""Collectives one device takes part in per call, in the counters' own
units: the sum over ``kind`` and ``axis`` of the program's
``dlaf_comm_collective_count_total``. The library counts a collective when
it traces a program, per executed step and per mesh axis it runs over
(``comm/collectives.py:_record``), and a cell's one program is traced once a
process, so the sum is one call's. The unit is a PER-AXIS RECORD, not an
all-reduce: ``bcast2d`` (the diagonal tile to the whole grid) is ONE
all-reduce over both axes and is recorded once on ``row`` and once on
``col``, as the byte counters charge its payload to each axis. The
distributed Cholesky at 16 steps on 2x2 reads 62 (31 an axis): 16 diagonal
broadcasts counted twice, 15 panel broadcasts along ``col``, 15
transposed-panel all-gathers along ``row``: 46 collectives on the device.
At this size a collective moves at most 4 MiB (eight local 512 KiB tiles
of a panel), so their number and not their payload sets what they cost.
None where the registry holds no such counter (a one-device run traces no
collective)."""

COUNTER = "dlaf_comm_collective_count_total"


def read(run, name):
    values = [m["value"] for m in run.get("counters") or ()
              if m.get("name") == COUNTER]
    return float(sum(values)) if values else None
