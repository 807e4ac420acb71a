"""Tick driver: the `tick.partition` span per tick (the resource list, the
lane partition and the solver resolution, on the event loop), mean over
the window's ticks (`spans.py`; the traced run)."""

import spans


def read(r):
    return spans.mean_ms(r, lambda t, s, c: s.get("tick.partition", 0.0))
