"""Share of the slice products' multiply-accumulates that a uniform-shape
step body computes and then masks, in percent:
``dlaf_ozaki_masked_macs_total{route}`` over the sum of
``dlaf_ozaki_macs_total{route, kind}`` (real and padding alike), both summed
over ``route``. The scan-form local Cholesky gives every step of a
telescoped segment the segment's whole block: its panel product covers the
rows above the pivot, its bulk product the other triangle of the square (in
chunks of 4096 columns from 8192 rows on, each from its own diagonal down)
and, in the segment's later steps, the columns the factorization has left
behind. The library counts, per executed step, the output elements beyond
the stored triangle of the live trailing block at the depth each dot was
handed (``tile_ops/ozaki.py:live_outputs`` / ``_count_macs``), when it
traces the program; a cell's program is traced once a process, so the share
is that of one call. None where the registry holds no masked counter (a
tree before PR 31, or a program with no masked product)."""

MASKED = "dlaf_ozaki_masked_macs_total"
ALL = "dlaf_ozaki_macs_total"


def read(run, name):
    masked, total = None, 0.0
    for m in run.get("counters") or ():
        if m.get("name") == MASKED:
            masked = (masked or 0.0) + m["value"]
        elif m.get("name") == ALL:
            total += m["value"]
    if masked is None or not total:
        return None
    return 100.0 * masked / total
