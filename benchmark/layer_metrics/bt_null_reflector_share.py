"""Share of the reflector slots the application multiplies that hold no
reflector, in percent: ``null / (live + null)`` over
``dlaf_bt_b2t_reflectors_total{impl, kind}``, summed over ``impl``. The
chase leaves its reflectors in a uniform layout, every sweep padded to the
longest sweep's steps, and the blocked program pads the sweeps to whole
groups: sweep ``s`` has a reflector at step ``t`` only where its first row
``s + 1 + t b`` lies inside the matrix, yet every ``(s, t)`` slot is a column
of a staircase that is factorized and multiplied. The library counts both
kinds from shapes when it traces the program
(``eigensolver/back_transform.py:chase_reflector_slots``), once a process, so
the share is one call's; :func:`hand_count` is the same count written
independently, for the tests. 48.46% at n = 4096, b = G = 128. None where
the registry holds no such counter (the parent)."""

COUNTER = "dlaf_bt_b2t_reflectors_total"


def hand_count(n: int, b: int, group: int):
    """``(levels, live, null)`` from the published layout alone: ``n - 2``
    sweeps of ``ceil((n - 1) / b)`` steps; the blocked program multiplies
    ``ceil((n - 2) / group)`` groups of ``group`` sweeps at every step
    (``group`` 0: the sweeps form, a sweep a level)."""
    sweeps, steps = max(n - 2, 0), -(-(n - 1) // b) if n > 1 else 0
    live = sum(1 for s in range(sweeps) for t in range(steps)
               if s + 1 + t * b < n)
    if not group:
        return sweeps, live, sweeps * steps - live
    groups = -(-sweeps // group)
    return groups * steps, live, groups * group * steps - live


def read(run, name):
    slots = {"live": 0.0, "null": 0.0}
    for m in run.get("counters") or ():
        kind = m.get("labels", {}).get("kind")
        if m.get("name") == COUNTER and kind in slots:
            slots[kind] += m["value"]
    total = slots["live"] + slots["null"]
    return 100.0 * slots["null"] / total if total else None
