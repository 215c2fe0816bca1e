"""Payload the collectives of one call move, per device, in MiB: the sum
over ``kind`` and ``axis`` of the program's ``dlaf_comm_collective_bytes_total``
counters. The library counts them from shapes when it traces a program, per
executed step (a scan body's collectives times the scan's trip count), and a
cell's one program is traced once a process, so the sum is the traffic model
of one call. Payload, not bytes on a link: a ring all-reduce moves about
``2 (p - 1) / p`` of it over each link of its axis. None where the registry
holds no such counter (a one-device cell traces no collective)."""

MIB = float(2 ** 20)
COUNTER = "dlaf_comm_collective_bytes_total"


def read(run, name):
    values = [m["value"] for m in run.get("counters") or ()
              if m.get("name") == COUNTER]
    return sum(values) / MIB if values else None
