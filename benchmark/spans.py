"""The program's tick spans, summed per window tick, for the span readers.

While a `jax.profiler` capture runs, as it does over a `--trace 1` run's
window, the program's tracer (`doorman_tpu/obs/trace.py`) records its
spans, phase laps and gc pauses into its ring, timed on `perf_counter`
like the harness's tick records. A span belongs to the window tick its
start falls in: from the tick's `start` to `start` plus `wall_s`.

Under `server.tick` the Tick driver's sections are its direct children
(`tick.partition`, `tick.offload`, `tick.persist`, `stream.fanout`,
`tick.audit`, `tick.flightrec`, `tick.admission`); `tick.offload` holds
`tick.engine`, the executor's share, which holds the engine's phase laps.
So, per tick, the tick wall less the laps splits into: the partition, the
offload less the engine (the thread hop), the engine less its laps, the
flight record, the other sections, and the wall less all the direct
children (unattributed). `gc` pauses overlay the sections.

There is nothing to read (None) when the window has no tick, a window
tick has no `server.tick` span (a program that records none, as before
these spans existed), or the ring overflowed and dropped spans from
inside the window.
"""

from __future__ import annotations

import bisect

TICK = "server.tick"
GC = "gc"


def ring():
    """The tracer's events, oldest first, and the ring's capacity."""
    from doorman_tpu.obs import trace

    tracer = trace.default_tracer()
    return tracer.snapshot(), tracer.capacity


def per_tick(r):
    """[(seconds per span name, seconds of server.tick's direct children
    less laps and gc pauses)] for each window tick, or None."""
    from doorman_tpu.obs.trace import perf_to_us

    if not r.ticks:
        return None
    events, capacity = ring()
    starts = [perf_to_us(t["start"]) for t in r.ticks]
    ends = [s + t["wall_s"] * 1e6 for s, t in zip(starts, r.ticks)]
    if len(events) >= capacity and \
            events[0].ts + (events[0].dur or 0.0) >= starts[0]:
        return None
    sums = [{} for _ in r.ticks]
    children = [0.0] * len(r.ticks)
    tick_of = {}  # server.tick span id -> window tick
    inside = []
    for e in events:
        if e.ph != "X":
            continue
        i = bisect.bisect_right(starts, e.ts) - 1
        if i < 0 or e.ts > ends[i]:
            continue
        sums[i][e.name] = sums[i].get(e.name, 0.0) + e.dur * 1e-6
        if e.name == TICK:
            tick_of[e.span_id] = i
        inside.append((i, e))
    if sorted(tick_of.values()) != list(range(len(r.ticks))):
        return None
    for i, e in inside:
        if tick_of.get(e.parent_id) == i and e.name != GC \
                and not (e.cat or "").startswith("phase"):
            children[i] += e.dur * 1e-6
    return list(zip(sums, children))


def mean_ms(r, part):
    """Mean over the window's ticks of part(tick record, seconds per span
    name, seconds of the direct children), in ms; None with nothing to
    read."""
    ticks = per_tick(r)
    if ticks is None:
        return None
    out = [part(t, s, c) for t, (s, c) in zip(r.ticks, ticks)]
    return sum(out) / len(out) * 1e3
