"""Tick engine: per tick, the `tick.engine` span less the engine's phase
laps (`phase_s`): engine work no lap covers. Mean over the window's ticks
(`spans.py`; the traced run)."""

import spans


def read(r):
    return spans.mean_ms(
        r, lambda t, s, c: s.get("tick.engine", 0.0)
        - sum(t["phases"].values()))
