"""Sequential levels one application of the chase's reflectors takes:
``dlaf_bt_b2t_levels_total{impl}`` summed over ``impl``. The library counts
the trip count of the one ``lax.scan`` when it traces the program
(``eigensolver/back_transform.py:_count_slots``), and a cell's program is
traced once a process, so the sum is one call's: ``ceil((n - 2) / G) *
ceil((n - 1) / b)`` in the blocked form, G = the band on a TPU (1024 at
n = 4096, b = 128; ``bt_null_reflector_share.hand_count``), ``n - 2`` in the
sweeps form. Each level is a T factor and two thin products that wait for
the level before: the call's latency floor. None where the registry holds
no such counter (the parent)."""

COUNTER = "dlaf_bt_b2t_levels_total"


def read(run, name):
    values = [m["value"] for m in run.get("counters") or ()
              if m.get("name") == COUNTER]
    return float(sum(values)) if values else None
