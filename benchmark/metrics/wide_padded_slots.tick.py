"""Tick engine: slots the chunked engine shipped per tick
(`last_scope["padded"]`: the scatter blocks' bucketed widths Dw + Df, and
the table's slots besides on a tick that staged the whole table), mean
over the window's ticks: what the buckets and the whole-table path ship
beyond `wide_staged_slots.tick`. None where the engine records no such
counter."""


def read(r):
    got = [t["engines"]["resident_wide"]["scope"].get("padded")
           for t in r.ticks if "resident_wide" in t["engines"]]
    if not got or None in got:
        return None
    return sum(got) / len(got)
