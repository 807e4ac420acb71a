"""Tick driver: per tick, the harness's tick wall less the direct
children of `server.tick` (the sections): what no section covers. Mean
over the window's ticks (`spans.py`; the traced run)."""

import spans


def read(r):
    return spans.mean_ms(r, lambda t, s, c: t["wall_s"] - c)
