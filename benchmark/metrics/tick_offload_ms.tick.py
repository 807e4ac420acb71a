"""Tick driver: per tick, the `tick.offload` span less the `tick.engine`
span inside it: the hop to the executor thread and back, as the event
loop waits it out. Mean over the window's ticks (`spans.py`; the traced
run)."""

import spans


def read(r):
    return spans.mean_ms(
        r, lambda t, s, c: s.get("tick.offload", 0.0)
        - s.get("tick.engine", 0.0))
