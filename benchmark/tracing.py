"""From a profiler trace to the numbers the per-layer readers use.

`extract` reads the `.xplane.pb` that `jax.profiler` wrote and keeps the
device planes' `XLA Ops` events and the host annotations, the
benchmark's own (`bench.`) and the program's spans (`server.tick`,
`tick.`, `engine.`, `stream.`, `flightrec.`; `doorman_tpu/obs/trace.py`
mirrors each into an annotation while a capture runs), as plain rows.
`reduce` turns rows into:

  window_s   length of the `bench.traced` annotation (the traced part
             of the measured window)
  busy_s     union of the intervals in which an operation ran on a
             device, inside the window, averaged over the devices
  ops        device seconds per operation, outermost events only (the
             trace also lists the ops inside a while loop's body, one
             event per iteration, nested in the loop's own event)
  gaps       idle intervals of the device, the longest first, each named
             by the innermost host annotation on the chain that overlaps
             it most: the annotation that overlaps it most, then within
             it the one inside that overlaps it most, and so on down
  kernel_s   device seconds of the kernels' events that began inside the
             window (the launches of the traced ticks)

`tests/test_tracing.py` checks it on hand-made rows and on a small trace
recorded on the chip (`tests/data/trace_rows.json`).
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIXES = ("bench.", "server.tick", "tick.", "engine.",
                       "stream.", "flightrec.")
WINDOW = "bench.traced"


def extract(trace_dir: str) -> list:
    """[plane, line, name, start_ns, dur_ns] rows of one trace."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane.pb in {trace_dir}: {paths}")
    data = ProfileData.from_file(paths[0])
    rows = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(ANNOTATION_PREFIXES):
                    rows.append([plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)])
    return rows


def op_label(name: str, kernel_names=()) -> str:
    """A short name for a device op: the kernel's name for a kernel's
    custom call, else the HLO instruction's name (the trace names an op
    by its whole HLO text, `%fusion.20 = f32[...] fusion(...), ...`)."""
    for k in kernel_names:
        if k in name:
            return k
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def reduce(rows: list, kernel_names=(), top: int = 10) -> "dict | None":
    """The trace's numbers, or None when the rows hold no traced window
    or no device operation (nothing to read)."""
    win = [r for r in rows if r[2] == WINDOW]
    if not win:
        return None
    lo = min(r[3] for r in win)
    hi = max(r[3] + r[4] for r in win)
    dev_rows = [r for r in rows if r[0].startswith(DEVICE_PREFIX)
                and r[1] == OPS_LINE]
    planes = sorted({r[0] for r in dev_rows})
    if not planes:
        return None
    busy_total = 0.0
    gaps = []
    ops: dict = {}
    kernel_ns = 0.0
    for plane in planes:
        mine = sorted((r for r in dev_rows if r[0] == plane),
                      key=lambda r: (r[3], -r[4]))
        merged = _union(_clip([[r[3], r[3] + r[4]] for r in mine], lo, hi))
        busy_total += sum(e - s for s, e in merged)
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append([s, e])
        outer_end = float("-inf")
        for r in mine:
            label = op_label(r[2], kernel_names)
            if label in kernel_names and lo <= r[3] < hi:
                kernel_ns += r[4]
            if r[3] < outer_end:
                continue  # nested in an op already counted
            outer_end = r[3] + r[4]
            s, e = max(r[3], lo), min(r[3] + r[4], hi)
            if e > s:
                ops[label] = ops.get(label, 0.0) + (e - s)
    host = [r for r in rows if r[2].startswith(ANNOTATION_PREFIXES)
            and r[2] != WINDOW]

    def name_gap(s, e):
        """Down the chain of annotations nested in one another, at each
        level the one that overlaps [s, e] most (the longer on a tie, so
        the descent passes through it to what it holds)."""
        def overlap(r):
            return min(e, r[3] + r[4]) - max(s, r[3])

        name, a, b = "none", float("-inf"), float("inf")
        pool = [r for r in host if overlap(r) > 0]
        while True:
            inside = [r for r in pool if a <= r[3] and r[3] + r[4] <= b]
            if not inside:
                return name
            r = max(inside, key=lambda r: (overlap(r), r[4]))
            pool.remove(r)
            name, a, b = r[2], r[3], r[3] + r[4]

    gaps.sort(key=lambda g: g[0] - g[1])
    n = len(planes)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "devices": n,
        "ops": sorted(([k, v / 1e9] for k, v in ops.items()),
                      key=lambda kv: -kv[1])[:top],
        "kernel_s": kernel_ns / 1e9,
        "gaps": [[name_gap(s, e), (e - s) / 1e9] for s, e in gaps[:top]],
    }
