#!/usr/bin/env python3
"""Cost of one live obs span (enter + exit) on this host, in microseconds:
sink on without a profiler session, then inside one; and of one
``telemetry.call`` of a jitted no-op over calling it directly, sink off and
on (on, the first call remembers the program it dispatched and every later
one pays one dict lookup). Host-only: no device work is timed (PERF.md
reports the chip machine's numbers).

    python3 scripts/span_cost.py [out_dir]
"""

from __future__ import annotations

import functools
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def per_span_us(make, n: int = 20000, repeats: int = 5) -> float:
    """Median over ``repeats`` of the mean wall of ``n`` enter+exit pairs."""
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n):
            with make():
                pass
        walls.append((time.perf_counter() - t0) / n * 1e6)
    return statistics.median(walls)


def per_call_us(fn, n: int = 20000, repeats: int = 5) -> float:
    """Median over ``repeats`` of the mean wall of ``n`` calls of ``fn``."""
    walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        walls.append((time.perf_counter() - t0) / n * 1e6)
    return statistics.median(walls)


def dispatch_us(telemetry, toy, x) -> tuple:
    """``(direct, through telemetry.call)``: one dispatch of ``toy(x)``, in
    microseconds (dispatch only: nothing waits for the result)."""
    toy(x).block_until_ready()
    direct = per_call_us(lambda: toy(x))
    through = per_call_us(lambda: telemetry.call("span_cost.toy", toy, x))
    toy(x).block_until_ready()
    return direct, through


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    import dlaf_tpu
    from dlaf_tpu import obs
    from dlaf_tpu.obs import telemetry

    os.makedirs(out_dir, exist_ok=True)
    toy, x = jax.jit(lambda v: v), jnp.zeros((8,))
    call_off = dispatch_us(telemetry, toy, x)
    live = functools.partial(obs.span, "stage.fence", fenced=False)
    attrs = functools.partial(obs.span, "stage.native.band_chase",
                              fenced=False, n=2048, b=256, threads=13)
    off = per_span_us(live)             # nothing configured: the no-op
    dlaf_tpu.initialize(dlaf_tpu.Configuration(
        metrics_path=os.path.join(out_dir, "span_cost.jsonl")))
    on = per_span_us(live)
    on_attrs = per_span_us(attrs)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(out_dir, "span_cost_trace"),
                             profiler_options=opts)
    try:
        traced = per_span_us(live, n=5000, repeats=3)
    finally:
        jax.profiler.stop_trace()
    call_on = dispatch_us(telemetry, toy, x)
    print(f"[span_cost] platform={jax.devices()[0].platform} "
          f"off_us={off:.3f} sink_on_us={on:.3f} "
          f"sink_on_3attrs_us={on_attrs:.3f} sink_on_profiler_on_us="
          f"{traced:.3f}", flush=True)
    print(f"[span_cost] telemetry.call of a jitted no-op, us a dispatch: "
          f"sink_off direct={call_off[0]:.3f} call={call_off[1]:.3f} "
          f"(+{call_off[1] - call_off[0]:.3f}); sink_on direct="
          f"{call_on[0]:.3f} call={call_on[1]:.3f} "
          f"(+{call_on[1] - call_on[0]:.3f}); remembered="
          f"{telemetry.programs()}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1
         else tempfile.mkdtemp(prefix="span_cost_"))
