"""Tick driver: the garbage-collection pauses (`gc` events) that began
inside a tick, per tick, mean over the window's ticks (`spans.py`; the
traced run). An overlay: the pauses also count in the section they
interrupted."""

import spans


def read(r):
    return spans.mean_ms(r, lambda t, s, c: s.get(spans.GC, 0.0))
