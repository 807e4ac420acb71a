"""Tick driver: the `tick.flightrec` span per tick (the flight record:
its store digest over every resource, and the record), mean over the
window's ticks (`spans.py`; the traced run)."""

import spans


def read(r):
    return spans.mean_ms(r, lambda t, s, c: s.get("tick.flightrec", 0.0))
