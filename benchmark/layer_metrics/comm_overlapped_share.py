"""Share of a call's collectives that ``comm_lookahead`` emits ahead of a
bulk product, in percent: ``dlaf_comm_overlapped_total{algo, axis}`` over
``dlaf_comm_collective_count_total{kind, axis}``, both summed over their
labels and both in per-axis records (``bcast2d`` counts once on each axis in
both; ``algorithms/cholesky.py:chain_comm_counts``). The unrolled
distributed Cholesky hoists step k+1's whole panel chain (diagonal
broadcast, panel broadcast, transposed-panel all-gather) between step k's
strip and step k's bulk product, so every chain but the first counts: 58 of
62 at 16 steps on 2x2, 93.548%. Both counters are trace-time INTENT: what
the builder emitted ahead of the product, not what the device overlapped;
``phase_ms.comm`` beside it is the device time the collectives took. None
where either counter is absent (a one-device run, a tree without the
counter, a builder that hoists nothing)."""

OVERLAPPED = "dlaf_comm_overlapped_total"
ALL = "dlaf_comm_collective_count_total"


def read(run, name):
    hoisted, total = None, 0.0
    for m in run.get("counters") or ():
        if m.get("name") == OVERLAPPED:
            hoisted = (hoisted or 0.0) + m["value"]
        elif m.get("name") == ALL:
            total += m["value"]
    if hoisted is None or not total:
        return None
    return 100.0 * hoisted / total
