"""Tick engine: dirty slots the chunked engine staged per tick
(`last_scope["slots"]`: wants-only plus full slots, n_w + n_f), mean over
the window's ticks. None where the engine records no such counter."""


def read(r):
    got = [t["engines"]["resident_wide"]["scope"].get("slots")
           for t in r.ticks if "resident_wide" in t["engines"]]
    if not got or None in got:
        return None
    return sum(got) / len(got)
