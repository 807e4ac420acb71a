"""Device program: chunk rows in the chunked engine's solve scope per
tick (`solver/resident_wide.py`'s `last_scope["rows"]`, sampled after
each tick), mean over the window's ticks. A chunk row holds 4,096 slots;
the full 10 x 100,000 table is 250 rows. None where no tick ran the
chunked engine."""


def read(r):
    rows = [t["engines"]["resident_wide"]["scope"].get("rows")
            for t in r.ticks if "resident_wide" in t["engines"]]
    if not rows or None in rows:
        return None
    return sum(rows) / len(rows)
