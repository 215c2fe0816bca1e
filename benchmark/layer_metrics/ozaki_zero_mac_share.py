"""Share of the slice products' multiply-accumulates that multiply zero
padding, in percent: ``zero / (real + zero)`` over the program's
``dlaf_ozaki_macs_total{route, kind}`` counters, summed over ``route``. The
library counts them from the depth of the operands it hands each dot when it
traces a program, per executed step (``tile_ops/ozaki.py:_count_macs``), and
a cell's program is traced once a process, so the share is that of one call.
A zero-padded scan over uniform shift groups reads 3/7 = 42.9% at seven
slices (21 of 49 slots of a product, 12 of 28 of a syrk); ragged groups read
0. None where the registry holds no such counter."""

COUNTER = "dlaf_ozaki_macs_total"


def read(run, name):
    macs = {"real": 0.0, "zero": 0.0}
    for m in run.get("counters") or ():
        kind = m.get("labels", {}).get("kind")
        if m.get("name") == COUNTER and kind in macs:
            macs[kind] += m["value"]
    total = macs["real"] + macs["zero"]
    return 100.0 * macs["zero"] / total if total else None
